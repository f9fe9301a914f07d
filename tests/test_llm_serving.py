"""Generative predictor serving tests (VERDICT r3 item 1, serving half).

Covers the predictor plugin boundary extension (framework "generative"
joins the one-of, reference pkg/apis/serving/v1beta1/predictor.go:33-59),
the V1 predict shape, the v2 generate-extension routes, token
streaming over chunked HTTP, and tensor-parallel generation on the
virtual device mesh.
"""

import asyncio
import json

import numpy as np
import pytest

from kfserving_tpu.predictors.llm import (
    ByteTokenizer,
    GenerativeConfig,
    GenerativeModel,
)

pytestmark = pytest.mark.asyncio


def _write_model_dir(tmp_path, **overrides):
    d = tmp_path / "llm"
    d.mkdir(exist_ok=True)
    cfg = {
        "architecture": "decoder_tiny",
        "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                        "num_heads": 2, "intermediate_size": 128,
                        "max_seq": 64},
        "max_slots": 2,
        "max_seq": 64,
        "prefill_buckets": [16, 32, 64],
        "max_new_tokens": 8,
        "tokenizer": "byte",
    }
    cfg.update(overrides)
    (d / "config.json").write_text(json.dumps(cfg))
    return str(d)


# ------------------------------------------------------------ tokenizer


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    text = "hello, TPU ✨"
    ids = tok.encode(text)
    assert ids[0] == tok.bos_id
    assert tok.decode(ids[1:]) == text
    assert tok.decode(tok.encode(text, add_bos=False)) == text
    assert tok.vocab_size == 258


# ------------------------------------------------------------ predictor


async def test_generative_model_v1_predict(tmp_path):
    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    try:
        out = await model.predict(
            {"instances": ["hello", {"prompt": "hi", "max_tokens": 4,
                                     "temperature": 0.0}]})
        preds = out["predictions"]
        assert len(preds) == 2
        for p in preds:
            assert isinstance(p["text"], str)
            assert p["finish_reason"] in ("eos", "length")
            assert p["token_count"] >= 0
        assert preds[1]["token_count"] <= 4
        # Greedy determinism across calls.
        again = await model.predict({"instances": ["hello"]})
        assert again["predictions"][0]["text"] == preds[0]["text"]
    finally:
        await model.close()


async def test_ignore_eos_ends_an_answer_at_its_budget_alone(tmp_path):
    """The same model and prompt, its first greedy token made the EOS id:
    the answer is empty and ends `eos`; with `ignore_eos` in the model's
    config the token is content like any other and the budget ends it."""
    from kfserving_tpu.predictors import llm

    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    try:
        first = (await model.predict({"instances": [
            {"prompt": "hello", "max_tokens": 6, "logprobs": 1}]})
            )["predictions"][0]["logprobs"][0]["id"]
    finally:
        await model.close()
    finishes = {}
    for ignore in (False, True):
        model = GenerativeModel("gen", _write_model_dir(
            tmp_path, ignore_eos=ignore))
        model.load()
        assert model.config.ignore_eos is ignore
        assert model.engine.eos_id == (None if ignore else llm.EOS_ID)
        if not ignore:
            model.engine.eos_id = first
        try:
            out = (await model.predict({"instances": [
                {"prompt": "hello", "max_tokens": 6}]}))["predictions"][0]
        finally:
            await model.close()
        finishes[ignore] = (out["finish_reason"], out["token_count"])
    assert finishes == {False: ("eos", 0), True: ("length", 6)}
    assert llm.GenerativeConfig("decoder_tiny").ignore_eos is False


async def test_generative_model_validation(tmp_path):
    from kfserving_tpu.protocol.errors import InvalidInput

    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    try:
        with pytest.raises(InvalidInput):
            await model.predict({"instances": [{"not_prompt": 1}]})
        with pytest.raises(InvalidInput):
            await model.predict({"instances": []})
    finally:
        await model.close()


# --------------------------------------------------------- HTTP routes


async def test_generate_routes_over_http(tmp_path):
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            # V1 :generate
            async with s.post(f"{base}/v1/models/gen:generate",
                              json={"prompt": "abc",
                                    "max_tokens": 5}) as r:
                assert r.status == 200, await r.text()
                out = await r.json()
            assert out["model_name"] == "gen"
            assert isinstance(out["text_output"], str)
            assert out["details"]["finish_reason"] in ("eos", "length")
            # v2 generate extension shape
            async with s.post(
                    f"{base}/v2/models/gen/generate",
                    json={"text_input": "abc",
                          "parameters": {"max_tokens": 5}}) as r:
                assert r.status == 200, await r.text()
                out2 = await r.json()
            assert out2["text_output"] == out["text_output"]  # greedy
            # predict still works alongside
            async with s.post(f"{base}/v1/models/gen:predict",
                              json={"instances": ["abc"]}) as r:
                assert r.status == 200
            # a non-generative route check: unknown model 404s
            async with s.post(f"{base}/v1/models/nope:generate",
                              json={"prompt": "x"}) as r:
                assert r.status == 404
            # metadata reports the generative platform
            async with s.get(f"{base}/v2/models/gen") as r:
                meta = await r.json()
            assert meta["platform"] == "jax-generate"
            assert meta["max_slots"] == 2
    finally:
        await server.stop_async()


async def test_generate_stream_chunks_arrive_incrementally(tmp_path):
    """The streaming surface: SSE events ride chunked transfer, tokens
    arrive progressively, and their concatenation equals the
    non-streaming result."""
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}/v1/models/gen:generate",
                              json={"prompt": "stream me",
                                    "max_tokens": 6}) as r:
                reference = (await r.json())["text_output"]
            events = []
            async with s.post(
                    f"{base}/v2/models/gen/generate_stream",
                    json={"text_input": "stream me",
                          "max_tokens": 6}) as r:
                assert r.status == 200
                assert r.headers.get("Content-Type",
                                     "").startswith("text/event-stream")
                buffer = b""
                async for chunk in r.content.iter_any():
                    buffer += chunk
                for line in buffer.decode().splitlines():
                    if line.startswith("data: "):
                        events.append(json.loads(line[6:]))
        assert len(events) >= 2  # tokens arrived as separate events
        text = "".join(e["token"]["text"] for e in events
                       if "token" in e)
        assert text == reference
        final = events[-1]
        assert final["finish_reason"] in ("eos", "length")
        assert final["generated_text"] == reference
    finally:
        await server.stop_async()


async def test_generate_stream_via_v1_stream_flag(tmp_path):
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                    f"http://127.0.0.1:{server.http_port}"
                    "/v1/models/gen:generate",
                    json={"prompt": "x", "max_tokens": 3,
                          "stream": True}) as r:
                assert r.status == 200
                body = await r.read()
        assert body.count(b"data: ") >= 1
    finally:
        await server.stop_async()


async def test_generate_stream_bad_request_is_clean_4xx(tmp_path):
    """Stream validation is eager: a prompt longer than the largest
    prefill bucket gets a clean 400 BEFORE any streaming headers — not
    a 200 followed by a dropped connection (code-review r4)."""
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                    f"http://127.0.0.1:{server.http_port}"
                    "/v2/models/gen/generate_stream",
                    json={"text_input": "x" * 500}) as r:
                assert r.status == 400
                body = await r.json()
            assert "exceeds" in body["error"]
            # Non-generative models reject the route cleanly too.
            async with s.post(
                    f"http://127.0.0.1:{server.http_port}"
                    "/v2/models/gen/generate_stream",
                    json={"wrong": 1}) as r:
                assert r.status == 400
    finally:
        await server.stop_async()


async def test_generate_stream_holds_admission_slot(tmp_path):
    """Streams go through the container_concurrency gate and hold the
    slot for their whole life — the longest-lived requests must not
    bypass the overload protection (code-review r4)."""
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(
        tmp_path, max_new_tokens=40))
    model.load()
    server = ModelServer(http_port=0, container_concurrency=1,
                         max_queue_depth=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            resp_a = await s.post(
                f"{base}/v2/models/gen/generate_stream",
                json={"text_input": "hold the slot",
                      "max_tokens": 40})
            assert resp_a.status == 200
            # Read ONE event so the stream is live and holding its slot.
            await resp_a.content.readany()
            # Second request of any verb sheds at the gate.
            async with s.post(f"{base}/v1/models/gen:predict",
                              json={"instances": ["x"]}) as r2:
                assert r2.status == 503
                assert "concurrency" in (await r2.json())["error"]
            # Drain A to completion: the slot frees...
            while not resp_a.content.at_eof():
                await resp_a.content.readany()
            resp_a.close()
            # ...and traffic flows again.
            for _ in range(50):
                async with s.post(
                        f"{base}/v1/models/gen:predict",
                        json={"instances": [
                            {"prompt": "x", "max_tokens": 2}]}) as r3:
                    if r3.status == 200:
                        break
                await asyncio.sleep(0.1)
            assert r3.status == 200
    finally:
        await server.stop_async()


# ------------------------------------------------------- control plane


async def test_generative_isvc_through_control_plane(tmp_path):
    """framework='generative' joins the predictor one-of: deploys
    through the controller, serves :generate via the ingress router."""
    import aiohttp

    from kfserving_tpu.control.controller import Controller
    from kfserving_tpu.control.orchestrator import InProcessOrchestrator
    from kfserving_tpu.control.router import IngressRouter
    from kfserving_tpu.control.spec import InferenceService, PredictorSpec

    model_dir = _write_model_dir(tmp_path)
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
        async with aiohttp.ClientSession() as s:
            async with s.post(
                    f"http://127.0.0.1:{router.http_port}"
                    "/v1/models/writer:generate",
                    json={"prompt": "abc", "max_tokens": 4}) as r:
                assert r.status == 200, await r.text()
                out = await r.json()
        assert out["model_name"] == "writer"
        assert out["details"]["token_count"] <= 4
    finally:
        await router.stop_async()
        await orch.shutdown()


# ------------------------------------------------------ tensor parallel


@pytest.mark.slow
async def test_generation_parity_under_tp_mesh(tmp_path):
    """Tensor-parallel generation on the virtual mesh: tp=2 sharded
    decode produces the same greedy tokens as unsharded — params shard
    per Megatron rules, the KV cache shards on heads."""
    unsharded = GenerativeModel("gen", _write_model_dir(tmp_path))
    unsharded.load()
    sharded = GenerativeModel(
        "gen2", _write_model_dir(tmp_path),
        config_overrides={"mesh": {"tp": 2}})
    sharded.load()
    try:
        a = await unsharded.predict({"instances": ["parity check"]})
        b = await sharded.predict({"instances": ["parity check"]})
        assert a["predictions"][0]["text"] == b["predictions"][0]["text"]
        assert (a["predictions"][0]["token_count"]
                == b["predictions"][0]["token_count"])
    finally:
        await unsharded.close()
        await sharded.close()


def test_hbm_accounting_includes_cache(tmp_path):
    from kfserving_tpu.engine.hbm import HBMManager

    hbm = HBMManager(budget_bytes=1 << 30)
    model = GenerativeModel("gen", _write_model_dir(tmp_path), hbm=hbm)
    model.load()
    try:
        resident = hbm.used_bytes
        # params + cache: cache alone is 2 layers * k+v * 2 slots *
        # 64 seq * 2 heads * 32 dim * 4B = 262144
        assert resident > model.engine.cache_bytes()
        assert model.engine.cache_bytes() == 2 * 2 * 2 * 64 * 2 * 32 * 4
    finally:
        model.unload()
    assert hbm.used_bytes == 0


async def test_generate_stream_disconnect_releases_slot(tmp_path):
    """A client that disconnects before (or right after) the stream
    starts must release BOTH the admission slot and the engine decode
    slot.  Before the round-5 fix, _respond returned early on a closed
    transport without ever aclose()ing the body, leaking one
    containerConcurrency slot per disconnect until the server wedged
    at all-503 (code-review r4 medium)."""
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(
        tmp_path, max_new_tokens=60))
    model.load()
    server = ModelServer(http_port=0, container_concurrency=1,
                         max_queue_depth=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        body = json.dumps({"text_input": "going away",
                           "max_tokens": 60}).encode()
        head = ("POST /v2/models/gen/generate_stream HTTP/1.1\r\n"
                "host: t\r\ncontent-type: application/json\r\n"
                f"content-length: {len(body)}\r\n\r\n").encode()
        # With container_concurrency=1, TWO leaks would wedge the
        # server; three disconnects prove release.
        for _ in range(3):
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.http_port)
            writer.write(head + body)
            await writer.drain()
            writer.close()  # vanish without reading a byte
            await writer.wait_closed()
            await asyncio.sleep(0.05)
        # Admission slot free again: a predict eventually succeeds.
        async with aiohttp.ClientSession() as s:
            r_ok = False
            for _ in range(100):
                async with s.post(
                        f"{base}/v1/models/gen:predict",
                        json={"instances": [
                            {"prompt": "x", "max_tokens": 2}]}) as r:
                    if r.status == 200:
                        r_ok = True
                        break
                await asyncio.sleep(0.1)
            assert r_ok, "admission slot leaked: predict never admitted"
        # Engine slots drained: cancel() fired for abandoned streams
        # instead of decoding 60 tokens for nobody.
        for _ in range(100):
            if (all(s is None for s in model.engine._slots)
                    and not model.engine._pending):
                break
            await asyncio.sleep(0.05)
        assert all(s is None for s in model.engine._slots)
    finally:
        await server.stop_async()


# ------------------------------------------------------ sampling surface


async def test_stop_sequence_truncates(tmp_path):
    """A stop string ends generation early: the result is clipped
    BEFORE the match, finish_reason is 'stop', and the engine slot is
    cancelled rather than decoding to the budget."""
    model = GenerativeModel("gen", _write_model_dir(
        tmp_path, max_new_tokens=24))
    model.load()
    try:
        base = await model._run_one(model._parse_instance(
            {"prompt": "abc", "max_tokens": 24}))
        full = base["text"]
        assert len(full) >= 4
        stop = full[2:4]  # guaranteed to occur in the greedy output
        res = await model._run_one(model._parse_instance(
            {"prompt": "abc", "max_tokens": 24, "stop": stop}))
        assert res["finish_reason"] == "stop"
        assert stop not in res["text"]
        assert res["text"] == full[:full.find(stop)]
        # The slot freed early: next request admits immediately.
        res2 = await model._run_one(model._parse_instance("abc"))
        assert res2["text"]
    finally:
        model.unload()


@pytest.mark.slow
async def test_stop_sequence_streaming_holdback(tmp_path):
    """Streaming with a stop sequence: no emitted chunk ever contains
    stop text (split-across-chunks included — K>1 makes chunks span
    multiple tokens), and the terminal generated_text is truncated."""
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(
        tmp_path, max_new_tokens=24, steps_per_call=4))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}/v2/models/gen/generate",
                              json={"text_input": "abc",
                                    "parameters": {
                                        "max_tokens": 24}}) as r:
                full = (await r.json())["text_output"]
            stop = full[3:5]
            want = full[:full.find(stop)]
            events = []
            async with s.post(
                    f"{base}/v2/models/gen/generate_stream",
                    json={"text_input": "abc", "max_tokens": 24,
                          "stop": stop}) as r:
                assert r.status == 200
                buffer = b""
                async for chunk in r.content.iter_any():
                    buffer += chunk
                for line in buffer.decode().splitlines():
                    if line.startswith("data: "):
                        events.append(json.loads(line[6:]))
        streamed = "".join(e["token"]["text"] for e in events
                           if "token" in e)
        assert stop not in streamed
        assert streamed == want
        final = events[-1]
        assert final["finish_reason"] == "stop"
        assert final["generated_text"] == want
    finally:
        await server.stop_async()


async def test_seed_reproducible_over_http(tmp_path):
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            texts = []
            for _ in range(2):
                async with s.post(
                        f"{base}/v2/models/gen/generate",
                        json={"text_input": "abc",
                              "parameters": {"max_tokens": 10,
                                             "temperature": 1.1,
                                             "seed": 1234}}) as r:
                    texts.append((await r.json())["text_output"])
        assert texts[0] == texts[1]
    finally:
        await server.stop_async()


async def test_logprobs_over_http(tmp_path):
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                    f"{base}/v2/models/gen/generate",
                    json={"text_input": "abc",
                          "parameters": {"max_tokens": 4,
                                         "logprobs": 2}}) as r:
                body = await r.json()
        lps = body["details"]["logprobs"]
        assert len(lps) == body["details"]["token_count"]
        for rec in lps:
            assert rec["logprob"] <= 0.0
            assert len(rec["top"]) == 2
            # greedy: the chosen token IS the top-1
            assert rec["top"][0]["id"] == rec["id"]
    finally:
        await server.stop_async()


async def test_sampling_params_rejected_cleanly(tmp_path):
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            for bad in ({"top_p": 0.0}, {"top_k": -2},
                        {"stop": [""]}, {"logprobs": 99}):
                async with s.post(
                        f"{base}/v2/models/gen/generate",
                        json={"text_input": "x",
                              "parameters": bad}) as r:
                    assert r.status == 400, (bad, await r.text())
    finally:
        await server.stop_async()


# ---------------------------------------------- streams through ingress


async def _router_fixture(model_dir, **isvc_kwargs):
    from kfserving_tpu.control.controller import Controller
    from kfserving_tpu.control.orchestrator import InProcessOrchestrator
    from kfserving_tpu.control.router import IngressRouter
    from kfserving_tpu.control.spec import (
        InferenceService,
        PredictorSpec,
    )

    orch = InProcessOrchestrator()
    controller = Controller(orch)
    router = IngressRouter(controller)
    await router.start_async()
    isvc = InferenceService(
        name="writer",
        predictor=PredictorSpec(framework="generative",
                                storage_uri=model_dir),
        **isvc_kwargs)
    status = await controller.apply(isvc)
    assert status.ready
    return router, controller, orch, isvc


async def test_generate_stream_through_ingress(tmp_path):
    """Token streams ride the ingress router: SSE chunks pass through
    unbuffered with canary/failover semantics applied at stream start
    (VERDICT r4 weak #2 — the flagship feature must not bypass the
    deployment machinery)."""
    import aiohttp

    router, controller, orch, _ = await _router_fixture(
        _write_model_dir(tmp_path, max_new_tokens=8))
    base = f"http://127.0.0.1:{router.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            # Reference result via the non-streaming routed verb.
            async with s.post(f"{base}/v1/models/writer:generate",
                              json={"prompt": "abc",
                                    "max_tokens": 6}) as r:
                assert r.status == 200, await r.text()
                want = (await r.json())["text_output"]
            events = []
            chunk_count = 0
            async with s.post(
                    f"{base}/v2/models/writer/generate_stream",
                    json={"text_input": "abc", "max_tokens": 6}) as r:
                assert r.status == 200, await r.text()
                assert r.headers["Content-Type"].startswith(
                    "text/event-stream")
                buffer = b""
                async for chunk in r.content.iter_any():
                    chunk_count += 1
                    buffer += chunk
                for line in buffer.decode().splitlines():
                    if line.startswith("data: "):
                        events.append(json.loads(line[6:]))
        assert chunk_count >= 2  # passed through, not buffered
        text = "".join(e["token"]["text"] for e in events
                       if "token" in e)
        assert text == want
        assert events[-1]["finish_reason"] in ("eos", "length")
        # The gauge drained when the stream ended.
        assert all(v == 0 for v in router.inflight.values()), \
            router.inflight
    finally:
        await router.stop_async()
        await orch.shutdown()


async def test_stream_flag_upgrade_through_ingress(tmp_path):
    """{"stream": true} on the routed :generate upgrades to SSE
    through the proxy (content-type detection, not route-based)."""
    import aiohttp

    router, controller, orch, _ = await _router_fixture(
        _write_model_dir(tmp_path))
    base = f"http://127.0.0.1:{router.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}/v1/models/writer:generate",
                              json={"prompt": "x", "max_tokens": 3,
                                    "stream": True}) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith(
                    "text/event-stream")
                body = await r.read()
        assert body.count(b"data: ") >= 1
    finally:
        await router.stop_async()
        await orch.shutdown()


@pytest.mark.slow
async def test_stream_canary_split_through_ingress(tmp_path):
    """Canary weights apply at stream START: with a 50% canary both
    revisions serve streams (deterministic rng seed drives the
    split)."""
    import aiohttp

    router, controller, orch, isvc = await _router_fixture(
        _write_model_dir(tmp_path, max_new_tokens=4))
    base = f"http://127.0.0.1:{router.http_port}"
    try:
        # Second revision: canary at 50 (a different storage_uri —
        # budget 3 instead of 4 — mints a new content-addressed
        # revision).
        d2 = tmp_path / "v2"
        d2.mkdir()
        isvc.predictor.storage_uri = _write_model_dir(
            d2, max_new_tokens=3)
        isvc.predictor.canary_traffic_percent = 50
        status = await controller.apply(isvc)
        assert status.ready
        key = f"{isvc.namespace}/{isvc.name}"
        cstatus = controller.reconciler.status[key].components[
            "predictor"]
        assert len([t for t in cstatus.traffic if t.percent > 0]) == 2
        served = set()
        async with aiohttp.ClientSession() as s:
            for _ in range(24):
                # No explicit max_tokens: each revision's config
                # default (4 vs 3) fingerprints which one served.
                async with s.post(
                        f"{base}/v2/models/writer/generate_stream",
                        json={"text_input": "abc"}) as r:
                    assert r.status == 200
                    buffer = await r.read()
                last = json.loads(
                    [ln for ln in buffer.decode().splitlines()
                     if ln.startswith("data: ")][-1][6:])
                served.add(last["details"]["token_count"])
        # Budgets 4 vs 3 distinguish the revisions.
        assert served == {3, 4}, served
    finally:
        await router.stop_async()
        await orch.shutdown()


async def test_stream_replica_death_yields_terminal_event(tmp_path):
    """A replica dying mid-stream (device failure, recycle past its
    drain budget) must surface to the routed client as a terminal SSE
    error event — never a silently dead socket."""
    import aiohttp

    router, controller, orch, isvc = await _router_fixture(
        _write_model_dir(tmp_path, max_new_tokens=50))
    base = f"http://127.0.0.1:{router.http_port}"
    try:
        cid = controller.reconciler.component_id(isvc, "predictor")
        replica = orch.replicas(cid)[0]
        model = replica.handle.repository.get_model("writer")
        events = []
        async with aiohttp.ClientSession() as s:
            async with s.post(
                    f"{base}/v2/models/writer/generate_stream",
                    json={"text_input": "abc"}) as r:
                assert r.status == 200
                buffer = b""
                injected = False
                try:
                    async for chunk in r.content.iter_any():
                        buffer += chunk
                        if not injected and b"data: " in buffer:
                            injected = True
                            # Simulate the device dying under the
                            # replica mid-generation.
                            model.engine._fail_all(
                                "error: injected device failure")
                except aiohttp.ClientError:
                    pytest.fail("routed client saw a dead socket, "
                                "not a terminal event")
        for line in buffer.decode().splitlines():
            if line.startswith("data: "):
                events.append(json.loads(line[6:]))
        assert events, buffer
        assert events[-1].get("finish_reason") == "error", events[-1]
        assert "error" in events[-1]
        assert all(v == 0 for v in router.inflight.values())
    finally:
        await router.stop_async()
        await orch.shutdown()


async def test_server_drain_waits_for_streams(tmp_path):
    """drain() sees a live token stream as in-flight work: False while
    it runs, True once it completes — the SIGTERM grace path that lets
    a recycle finish generations instead of killing them."""
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    import time as _time

    model = GenerativeModel("gen", _write_model_dir(
        tmp_path, max_new_tokens=50))
    model.load()
    server = ModelServer(http_port=0, container_concurrency=4)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    # Tiny CPU decode finishes ~50 tokens in milliseconds; stretch the
    # wave cadence so the stream is verifiably live during drain.
    orig_fetch = model.engine._fetch_wave

    def slow_fetch(toks_h, lp_h):
        _time.sleep(0.05)
        return orig_fetch(toks_h, lp_h)

    model.engine._fetch_wave = slow_fetch
    try:
        async with aiohttp.ClientSession() as s:
            resp = await s.post(
                f"{base}/v2/models/gen/generate_stream",
                json={"text_input": "hold", "max_tokens": 50})
            assert resp.status == 200
            await resp.content.readany()  # stream live
            assert await server.drain(0.3) is False
            while not resp.content.at_eof():
                await resp.content.readany()
            resp.close()
            assert await server.drain(10.0) is True
    finally:
        await server.stop_async()


@pytest.mark.slow
async def test_autoscaler_scales_on_slot_occupancy(tmp_path):
    """Scale-up driven PURELY by engine slot saturation at low request
    count: 2 slots busy + pending prefills with a near-zero router
    gauge must still add replicas (VERDICT r4 #8 — request count
    cannot see stream-saturated replicas)."""
    from kfserving_tpu.control.autoscaler import Autoscaler

    router, controller, orch, isvc = await _router_fixture(
        _write_model_dir(tmp_path, max_slots=2, max_new_tokens=50))
    isvc.predictor.max_replicas = 3
    await controller.apply(isvc)
    scaler = Autoscaler(controller, router, tick_seconds=0.01)
    cid = controller.reconciler.component_id(isvc, "predictor")
    try:
        model = orch.replicas(cid)[0].handle.repository.get_model(
            "writer")
        eng = model.engine
        # Stretch wave cadence so the slots stay verifiably busy.
        orig_fetch = eng._fetch_wave

        def slow_fetch(toks_h, lp_h):
            import time as _t

            _t.sleep(0.05)
            return orig_fetch(toks_h, lp_h)

        eng._fetch_wave = slow_fetch
        # Saturate: both slots + 2 queued prefills, NO routed traffic.
        reqs = [eng.submit([1, 2, 3], max_new_tokens=50)
                for _ in range(4)]
        # First prefill pays the compile; poll until the pool shows
        # saturated.
        for _ in range(300):
            g = eng.load_gauges()
            if g["active_slots"] == 2 and g["pending"] >= 1:
                break
            await asyncio.sleep(0.1)
        assert g["active_slots"] == 2 and g["pending"] >= 1, g
        assert router.inflight.get("router/writer/predictor", 0) == 0
        # busy=4 vs capacity 0.8*2 -> ceil(4/1.6)=3 replicas (clamped).
        await scaler.tick()
        assert len(orch.replicas(cid)) == 3
        # Load gone -> the same signal scales back down to the floor.
        for r in reqs:
            eng.cancel(r)
        for _ in range(8):
            await scaler.tick()
        assert len(orch.replicas(cid)) == 1
    finally:
        await router.stop_async()
        await orch.shutdown()


# ------------------------------------------------ incremental decoder


def test_incremental_decoder_multibyte_across_tokens():
    """A UTF-8 char split across tokens must never surface as U+FFFD
    mid-stream nor be dropped — the partial byte is held until it
    completes (code-review r5: char-index slicing dropped it)."""
    from kfserving_tpu.predictors.llm import IncrementalDecoder

    tok = ByteTokenizer()
    text = "héllo ✨ wörld"
    ids = tok.encode(text, add_bos=False)
    dec = IncrementalDecoder(tok, [])
    out = ""
    for t in ids:
        delta, stopped = dec.push(t)
        assert not stopped
        assert "�" not in delta
        out += delta
    out += dec.finish()
    assert out == text == dec.text()
    assert not dec.degraded


def test_incremental_decoder_stop_spans_tokens():
    from kfserving_tpu.predictors.llm import IncrementalDecoder

    tok = ByteTokenizer()
    dec = IncrementalDecoder(tok, ["END"])
    emitted = ""
    stopped = False
    for ch in "abcENDxyz":
        delta, stopped = dec.push(ord(ch))
        emitted += delta
        if stopped:
            break
    assert stopped
    assert emitted == dec.text() == "abc"  # stop text never leaked


def test_incremental_decoder_window_stays_bounded():
    """Per-token work is O(window): the pending window compacts, so a
    long generation never re-decodes its whole history."""
    from kfserving_tpu.predictors.llm import IncrementalDecoder

    tok = ByteTokenizer()
    dec = IncrementalDecoder(tok, ["ZZZ"])
    for _ in range(500):
        dec.push(ord("a"))
    assert len(dec._pending) <= dec._KEEP + 1
    assert dec.text() == "a" * 500


def test_incremental_decoder_degraded_mode_still_matches_stops():
    """A tokenizer whose decode rewrites already-emitted text flips
    the decoder into degraded mode; stop sequences must STILL
    truncate (ADVICE r5: they were silently disabled), via full
    re-decode."""
    from kfserving_tpu.predictors.llm import IncrementalDecoder

    class _RewritingTok:
        # Joint cleanup rewrites "ab" -> "AB" once both tokens are
        # present (sentencepiece-style non-append-stable decode).
        def decode(self, ids):
            return "".join(chr(i) for i in ids).replace("ab", "AB")

    dec = IncrementalDecoder(_RewritingTok(), ["E"])
    stopped_at = None
    for i, ch in enumerate("abcEx"):
        _, stopped = dec.push(ord(ch))
        if stopped:
            stopped_at = i
            break
    assert dec.degraded
    assert stopped_at == 3           # the "E" push matched
    assert dec.text() == "ABc"       # truncated BEFORE the stop text


def test_incremental_decoder_degraded_without_stops_stays_silent():
    from kfserving_tpu.predictors.llm import IncrementalDecoder

    class _RewritingTok:
        def decode(self, ids):
            return "".join(chr(i) for i in ids).replace("ab", "AB")

    dec = IncrementalDecoder(_RewritingTok(), [])
    for ch in "abcd":
        _, stopped = dec.push(ord(ch))
        assert not stopped
    assert dec.degraded
    # Terminal text comes from the caller's full decode in this mode.
    assert dec.finish() == ""


def test_incremental_decoder_trailing_partial_flushes_at_finish():
    from kfserving_tpu.predictors.llm import IncrementalDecoder

    tok = ByteTokenizer()
    dec = IncrementalDecoder(tok, [])
    delta, _ = dec.push(0xC3)  # first byte of a 2-byte char
    assert delta == ""         # held, not U+FFFD
    tail = dec.finish()        # genuine truncation: flush as U+FFFD
    assert tail == "�"


async def test_startup_phases_reported(tmp_path):
    """Boot-phase self-reporting (VERDICT r4 weak #4): the server
    exposes cumulative since-process-birth marks so a recycle's
    successor load time is explainable, not a mystery number."""
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model = GenerativeModel("gen", _write_model_dir(tmp_path))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(
                    f"http://127.0.0.1:{server.http_port}"
                    "/startup_phases") as r:
                assert r.status == 200
                phases = await r.json()
        for key in ("interpreter_imports", "load_start", "download",
                    "init_params", "serving"):
            assert key in phases, (key, phases)
        # Cumulative and ordered: load pipeline marks never decrease.
        assert (phases["load_start"] <= phases["download"]
                <= phases["init_params"] <= phases["serving"])
        assert phases["interpreter_imports"] > 0
    finally:
        await server.stop_async()


# ------------------------------------------------ parameter residency

_DRAFT = {"tokens": 2, "draft": {
    "architecture": "decoder_tiny",
    "arch_kwargs": {"num_layers": 1, "hidden_size": 32, "num_heads": 2,
                    "intermediate_size": 64, "max_seq": 64},
    "window": 8}}


@pytest.mark.parametrize("speculative", [None, _DRAFT],
                         ids=["target", "target+draft"])
async def test_param_cache_hit_is_placed_on_device(tmp_path,
                                                   speculative):
    """A param_cache hit hands over read-only memmap views; after
    load() no parameter leaf of the engine (or of its draft) is a host
    array and each sits where the engine's pool sits."""
    import jax

    overrides = {"block_size": 16, "prefill_buckets": [16, 32, 64]}
    if speculative:
        overrides["speculative"] = speculative
    model_dir = _write_model_dir(tmp_path, **overrides)
    first = GenerativeModel("first", model_dir)
    first.load()          # materializes and stores the entry
    await first.close()
    model = GenerativeModel("gen", model_dir)
    model.load()
    try:
        assert model.param_source == "mmap"
        engine = model.engine
        pool_devices = engine._caches[0][0].devices()
        trees = [engine.variables]
        if speculative:
            trees.append(engine.draft_variables)
            # The residency handle reads the placed tree too: no
            # second (host) tree stays alive beside it.
            assert model._draft_handle.variables \
                is engine.draft_variables
            assert (model._draft_handle.param_bytes()
                    == engine.draft_param_bytes() > 0)
        else:
            assert engine.draft_variables is None
        for tree in trees:
            leaves = jax.tree.leaves(tree)
            assert leaves
            for leaf in leaves:
                assert isinstance(leaf, jax.Array), type(leaf)
                assert not isinstance(leaf, np.ndarray)
                assert leaf.devices() == pool_devices
        assert (engine.stats()["params_resident_bytes"]
                == engine.param_bytes() + engine.draft_param_bytes())
        out = await model.predict({"instances": ["resident"]})
        assert out["predictions"][0]["token_count"] > 0
    finally:
        await model.close()


async def test_params_resident_bytes_on_metrics_and_startup_phases(
        tmp_path):
    """The mechanism says it engaged: `params_device` follows
    `params_mmap` under /startup_phases, and /metrics carries the
    resident bytes as a family of the generator's own (once: the
    generic per-key engine export leaves it out)."""
    import aiohttp

    from kfserving_tpu.server.app import ModelServer

    model_dir = _write_model_dir(tmp_path, speculative=_DRAFT,
                                 block_size=16)
    first = GenerativeModel("first", model_dir)
    first.load()
    await first.close()
    model = GenerativeModel("gen", model_dir)
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/startup_phases") as r:
                phases = await r.json()
            async with s.get(f"{base}/metrics") as r:
                text = await r.text()
        assert phases["params_mmap"] <= phases["params_device"] \
            <= phases["serving"], phases
        want = (model.engine.param_bytes()
                + model.engine.draft_param_bytes())
        lines = [ln for ln in text.splitlines()
                 if "params_resident_bytes" in ln
                 and not ln.startswith("#")]
        assert len(lines) == 1, lines
        name, value = lines[0].rsplit(" ", 1)
        assert name == ('kfserving_tpu_generator_params_resident_bytes'
                        '{model="gen"}')
        assert float(value) == want
    finally:
        await server.stop_async()


def test_exit_with_parent_ends_a_server_whose_parent_was_killed(tmp_path):
    """`"exit_with_parent": true` in the model's config
    (`startup.exit_with_parent`): a parent that is killed outright, with
    no chance to stop what it started, takes the child with it; without
    the call the child lives on."""
    import os
    import signal
    import subprocess
    import sys
    import time

    assert GenerativeConfig("decoder_tiny").exit_with_parent is False
    assert GenerativeConfig(
        "decoder_tiny", exit_with_parent=True).exit_with_parent is True
    child = ("import sys, time; from kfserving_tpu import startup; "
             "sys.argv[1] == 'tied' and startup.exit_with_parent(); "
             "print('up', flush=True); time.sleep(120)")
    parent = ("import subprocess, sys, time; "
              f"p = subprocess.Popen([sys.executable, '-c', {child!r}, "
              "sys.argv[1]], stdout=subprocess.PIPE); p.stdout.readline(); "
              "print(p.pid, flush=True); time.sleep(120)")
    alive = {}
    for mode in ("tied", "free"):
        proc = subprocess.Popen([sys.executable, "-c", parent, mode],
                                stdout=subprocess.PIPE, text=True,
                                cwd=os.path.dirname(os.path.dirname(
                                    os.path.abspath(__file__))))
        pid = int(proc.stdout.readline())
        proc.kill()
        proc.wait()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                break
            with open(f"/proc/{pid}/stat") as f:
                if f.read().split(")")[1].split()[0] == "Z":
                    break
            if mode == "free":
                break
            time.sleep(0.1)
        try:
            os.kill(pid, 0)
            with open(f"/proc/{pid}/stat") as f:
                alive[mode] = f.read().split(")")[1].split()[0] != "Z"
            os.kill(pid, signal.SIGKILL)
        except (ProcessLookupError, FileNotFoundError):
            alive[mode] = False
    assert alive == {"tied": False, "free": True}
