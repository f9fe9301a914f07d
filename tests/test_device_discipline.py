"""Device-discipline tier (ISSUE 14): kfslint's XLA/JAX rules and the
KFS_SANITIZE runtime sanitizer.

Static half: per-rule edge cases for `host-sync`,
`jit-recompile-hazard`, `blocking-dispatch`, `prng-key-reuse` (the
golden FIRE/clean fixture contract lives in test_static_analysis.py
beside the PR-8 rules), plus regressions for the async-blocking
false-positive classes this PR fixed (awaited local callables,
executor-offload fakes) and the `--format github` CLI mode.

Dynamic half: the sanitizer's three mechanisms proven deterministically
— recompile-after-declared-warmup (via engine/compile_cache),
forbidden transfer under the armed loop guard, and the event-loop
stall watchdog — each asserting the violation counter AND the pinned
flight-recorder entry; a KFS_SANITIZE=0 no-op check; and the
fast-tier generate smoke: a real GenerationEngine run under
KFS_SANITIZE=1 with warmup + N decode steps and ZERO violations,
then fault-injected recompile and forbidden-transfer runs that are
provably caught.
"""

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfserving_tpu.tools import analyzers
from kfserving_tpu.tools.analyzers.__main__ import main as kfslint_main
from kfserving_tpu.tools.analyzers.core import analyze_source

MAX_SEQ = 64


def _rules():
    return analyzers.default_rules()


def _findings(src):
    return analyze_source(src, "x.py", _rules())


# ===================================================== static: host-sync
def test_host_sync_awaited_results_are_host_values():
    src = (
        "import numpy as np\n"
        "async def scheduler(engine):\n"
        "    fetched = await engine.next_wave()\n"
        "    return int(fetched[0]), np.asarray(fetched)\n")
    assert _findings(src) == []


def test_host_sync_inline_dispatch_result_fires():
    src = (
        "import jax.numpy as jnp\n"
        "async def wave(feed):\n"
        "    return float(jnp.sum(feed))\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("host-sync", 3)]


def test_host_sync_metadata_access_is_free():
    src = (
        "import jax.numpy as jnp\n"
        "async def wave(feed):\n"
        "    toks = jnp.argmax(feed, -1)\n"
        "    return int(toks.shape[0]) + int(toks.ndim)\n")
    assert _findings(src) == []


def test_host_sync_handle_param_convention():
    # `*_h` params are device handles; the rule only scopes to
    # wave/dispatch-named sync functions, so `merge` stays silent.
    src = (
        "import numpy as np\n"
        "def fetch_wave(toks_h):\n"
        "    return np.asarray(toks_h)\n"
        "def merge(toks_h):\n"
        "    return np.asarray(toks_h)\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("host-sync", 3)]


def test_host_sync_tree_map_lambda_fetch():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def execute_batch(params, x):\n"
        "    out = jnp.tanh(x)\n"
        "    return jax.tree.map(lambda a: np.asarray(a), out)\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("host-sync", 6)]


def test_host_sync_reassignment_from_executor_kills_taint():
    # The idiomatic refetch-through-the-executor into the SAME name:
    # after `toks = await loop.run_in_executor(...)` the name is a
    # host value and sinks over it are free.
    src = (
        "import jax.numpy as jnp\n"
        "async def wave(feed, loop, ex, fetch):\n"
        "    toks = jnp.argmax(feed, -1)\n"
        "    toks = await loop.run_in_executor(ex, fetch, toks)\n"
        "    return int(toks[0])\n")
    assert _findings(src) == []


def test_host_sync_test_functions_exempt():
    src = (
        "import jax.numpy as jnp\n"
        "async def test_decode_parity(feed):\n"
        "    return float(jnp.sum(feed))\n")
    assert _findings(src) == []


def test_host_sync_sanctioned_pragma_suppresses():
    src = (
        "import numpy as np\n"
        "def fetch_wave(toks_h):\n"
        "    # kfslint: disable=host-sync — sanctioned fetch site\n"
        "    return np.asarray(toks_h)\n")
    assert _findings(src) == []


def test_live_fetch_sites_carry_sanctioned_pragmas():
    # The two real fetch points must stay pragma'd (and so silent):
    # un-pragma'd analysis of the same files DOES fire, proving the
    # pragmas are load-bearing rather than the rule being blind.
    import kfserving_tpu.engine.generator as gen_mod
    import kfserving_tpu.engine.jax_engine as eng_mod
    for mod in (gen_mod, eng_mod):
        with open(mod.__file__) as f:
            src = f.read()
        silent = analyze_source(src, mod.__file__, _rules())
        assert [f for f in silent if f.rule == "host-sync"] == []
        loud = analyze_source(src, mod.__file__, _rules(),
                              respect_pragmas=False)
        assert [f for f in loud if f.rule == "host-sync"], \
            f"{mod.__file__}: expected sanctioned-fetch findings " \
            f"with pragmas ignored"


# ========================================= static: jit-recompile-hazard
def test_recompile_bucketed_size_is_cleansed():
    src = (
        "import jax\n"
        "step = jax.jit(lambda p, x: x)\n"
        "def dispatch(p, req, buckets):\n"
        "    n = len(req.tokens)\n"
        "    step(p, buckets.fit(n))\n")
    assert _findings(src) == []


def test_recompile_raw_len_fires():
    src = (
        "import jax\n"
        "step = jax.jit(lambda p, x: x)\n"
        "def dispatch(p, req):\n"
        "    step(p, len(req.tokens))\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("jit-recompile-hazard", 4)]


def test_recompile_ctor_shape_taint_and_display_laundering():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "step = jax.jit(lambda p, x: x)\n"
        "def dispatch(p, req):\n"
        "    n = int(req.ids.size)\n"
        "    step(p, np.asarray([n], np.int32))\n"   # static shape
        "    x = np.zeros((n, 8))\n"
        "    step(p, x)\n")                          # dynamic shape
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("jit-recompile-hazard", 8)]


def test_recompile_static_argnums_fstring():
    src = (
        "import jax\n"
        "render = jax.jit(lambda x, m: x, static_argnums=(1,))\n"
        "def go(x, mode):\n"
        "    render(x, f'm-{mode}')\n"
        "    render(x, 'greedy')\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("jit-recompile-hazard", 4)]


def test_recompile_decorated_jit_collected():
    src = (
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnums=(1,))\n"
        "def kernel(x, mode):\n"
        "    return x\n"
        "def go(x):\n"
        "    kernel(x, [1])\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("jit-recompile-hazard", 7)]


# ============================================ static: blocking-dispatch
def test_blocking_dispatch_async_and_under_lock():
    src = (
        "import threading\n"
        "import jax\n"
        "step = jax.jit(lambda p, x: x)\n"
        "_lock = threading.Lock()\n"
        "async def h(p, x):\n"
        "    return step(p, x)\n"
        "def flush(p, x):\n"
        "    with _lock:\n"
        "        out = step(p, x)\n"
        "    return step(p, out)\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("blocking-dispatch", 6), ("blocking-dispatch", 9)]


def test_blocking_dispatch_offloaded_reference_clean():
    src = (
        "import jax\n"
        "step = jax.jit(lambda p, x: x)\n"
        "async def h(loop, p, x):\n"
        "    return await loop.run_in_executor(None, step, p, x)\n")
    assert _findings(src) == []


def test_blocking_dispatch_lock_in_test_function_exempt():
    # The scoping policy covers the lock branch too: a test may hold
    # its own lock around a jitted call.
    src = (
        "import threading\n"
        "import jax\n"
        "step = jax.jit(lambda p, x: x)\n"
        "_lock = threading.Lock()\n"
        "def test_decode_under_lock(p, x):\n"
        "    with _lock:\n"
        "        return step(p, x)\n")
    assert _findings(src) == []


def test_blocking_dispatch_lock_in_async_def_reported_once():
    # One call, one finding — the lock diagnosis wins over the
    # generic on-the-loop one.
    src = (
        "import threading\n"
        "import jax\n"
        "step = jax.jit(lambda p, x: x)\n"
        "_lock = threading.Lock()\n"
        "async def h(p, x):\n"
        "    with _lock:\n"
        "        return step(p, x)\n")
    findings = _findings(src)
    assert [(f.rule, f.line) for f in findings] == \
        [("blocking-dispatch", 7)]
    assert "under held lock" in findings[0].message


def test_blocking_dispatch_asyncio_lock_not_a_threadlock():
    src = (
        "import asyncio\n"
        "import jax\n"
        "step = jax.jit(lambda p, x: x)\n"
        "_alock = asyncio.Lock()\n"
        "def flush(p, x):\n"
        "    with _alock:\n"
        "        return step(p, x)\n")
    assert _findings(src) == []


# ============================================== static: prng-key-reuse
def test_prng_reuse_fires_second_consume():
    src = (
        "import jax\n"
        "def sample(shape):\n"
        "    k = jax.random.PRNGKey(0)\n"
        "    a = jax.random.normal(k, shape)\n"
        "    b = jax.random.uniform(k, shape)\n"
        "    return a, b\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("prng-key-reuse", 5)]


def test_prng_split_and_fold_in_are_clean():
    src = (
        "import jax\n"
        "def sample(shape):\n"
        "    k = jax.random.PRNGKey(0)\n"
        "    k1, k2 = jax.random.split(k)\n"
        "    a = jax.random.normal(k1, shape)\n"
        "    b = jax.random.normal(k2, shape)\n"
        "    c = [jax.random.normal(jax.random.fold_in(k1, i), shape)\n"
        "         for i in range(3)]\n"
        "    return a, b, c\n")
    # fold_in's first arg is a Call, not a tracked name; k1's single
    # tracked consume stays single.
    assert _findings(src) == []


def test_prng_loop_reuse_without_resplit_fires_once():
    src = (
        "import jax\n"
        "def sample(shape):\n"
        "    k = jax.random.PRNGKey(0)\n"
        "    out = []\n"
        "    for _ in range(4):\n"
        "        out.append(jax.random.normal(k, shape))\n"
        "    return out\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("prng-key-reuse", 6)]


def test_prng_branch_exclusive_consumes_are_clean():
    # Exactly one branch draws per call: no correlation possible.
    src = (
        "import jax\n"
        "def sample(key, greedy, shape):\n"
        "    if greedy:\n"
        "        return jax.random.categorical(key, shape)\n"
        "    else:\n"
        "        return jax.random.uniform(key, shape)\n")
    assert _findings(src) == []


def test_prng_consume_before_and_inside_branch_still_fires():
    src = (
        "import jax\n"
        "def sample(key, flag, shape):\n"
        "    a = jax.random.normal(key, shape)\n"
        "    if flag:\n"
        "        b = jax.random.uniform(key, shape)\n"
        "    return a\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("prng-key-reuse", 5)]


def test_prng_resplit_inside_loop_is_clean():
    src = (
        "import jax\n"
        "def sample(shape):\n"
        "    k = jax.random.PRNGKey(0)\n"
        "    for _ in range(4):\n"
        "        k, sub = jax.random.split(k)\n"
        "        jax.random.normal(sub, shape)\n")
    assert _findings(src) == []


# ============================ static: async-blocking FP regressions
def test_awaited_local_callable_not_matched_to_sync_def():
    # The PR 14 retry.call class: `await call(payload)` must never
    # match a same-named sync def elsewhere in the tree.
    from kfserving_tpu.tools.analyzers.core import analyze_snippets
    tree = {
        "retry.py": (
            "import time\n"
            "def call(fn):\n"
            "    time.sleep(1)\n"
            "    return fn()\n"),
        "bench.py": (
            "async def one(call, payload):\n"
            "    await call(payload)\n"),
    }
    assert analyze_snippets(tree, _rules()) == []


def test_executor_fake_does_not_poison_offloads():
    from kfserving_tpu.tools.analyzers.core import analyze_snippets
    tree = {
        "fake.py": (
            "import time\n"
            "def run_in_executor(ex, fn, *args):\n"
            "    time.sleep(0)\n"
            "    return fn(*args)\n"),
        "app.py": (
            "async def h(loop, helper):\n"
            "    await loop.run_in_executor(None, helper)\n"),
    }
    assert analyze_snippets(tree, _rules()) == []


def test_offload_argument_call_still_fires():
    # One-hop findings land in finalize(): use the full pipeline.
    from kfserving_tpu.tools.analyzers.core import analyze_snippets
    src = (
        "def _load():\n"
        "    return open('/tmp/x')\n"
        "async def h(loop):\n"
        "    await loop.run_in_executor(None, _load())\n")
    assert [(f.rule, f.line)
            for f in analyze_snippets({"x.py": src}, _rules())] == \
        [("async-blocking", 4)]


def test_async_test_functions_exempt_from_blocking_not_spinloop():
    src = (
        "import time\n"
        "async def test_setup(tmp_path):\n"
        "    time.sleep(0.1)\n"          # exempt: test harness
        "    while tmp_path.exists():\n"  # NOT exempt: livelock
        "        pass\n")
    assert [(f.rule, f.line) for f in _findings(src)] == \
        [("spin-loop", 4)]


# ================================================= CLI: --format github
def test_format_github_annotation_lines(capsys):
    import os
    fire = os.path.join(os.path.dirname(__file__), "fixtures",
                        "kfslint", "spin_loop_fire.py")
    rc = kfslint_main([fire, "--no-baseline", "--format", "github"])
    assert rc == 1
    out = capsys.readouterr().out.splitlines()
    assert out, "no annotations emitted"
    for line in out:
        assert line.startswith("::error file=")
        assert ",line=" in line and "::" in line[2:]
        assert "\n" not in line
    assert any("title=kfslint spin-loop" in line for line in out)


def test_format_github_reports_stale_baseline(tmp_path, capsys):
    import json
    import os
    clean = os.path.join(os.path.dirname(__file__), "fixtures",
                         "kfslint", "spin_loop_clean.py")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([{"rule": "spin-loop", "path": clean,
                               "snippet": "while gone:"}]))
    rc = kfslint_main([clean, "--baseline", str(bl),
                       "--format", "github"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "::error file=" in out and "stale-baseline" in out


# ======================================================= sanitizer unit
@pytest.fixture(autouse=True)
def _sanitizer_reset():
    from kfserving_tpu.reliability import sanitizer
    sanitizer.reset()
    yield
    sanitizer.reset()


@pytest.fixture
def recorder():
    from kfserving_tpu.observability.monitoring.flight_recorder import (
        FlightRecorder,
    )
    from kfserving_tpu.reliability import sanitizer
    rec = FlightRecorder()
    sanitizer.attach_flight_recorder(rec)
    return rec


def _pinned_reasons(rec):
    return [e.get("pinned") for e in rec.dump(100)["pinned"]]


def test_sanitize_off_is_a_true_noop(monkeypatch, recorder):
    from kfserving_tpu.observability import REGISTRY
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.delenv("KFS_SANITIZE", raising=False)
    assert not sanitizer.enabled()
    # Hot-path hooks degrade to env reads: no arming, no counting,
    # no jax transfer guard (the implicit transfer below succeeds).
    sanitizer.declare_warmup_complete("src")
    sanitizer.note_compilation("src", ("decode", 8))
    with sanitizer.loop_guard("src"):
        assert float(jnp.arange(3)[0]) == 0.0
    with sanitizer.sanctioned_fetch():
        pass
    assert sanitizer.violations() == {}
    assert _pinned_reasons(recorder) == []
    assert "kfserving_tpu_sanitizer_violations_total" \
        not in REGISTRY.sample_names()
    assert sanitizer.start_watchdog(None) is None


def test_recompile_after_declared_warmup(monkeypatch, recorder):
    from kfserving_tpu.engine import compile_cache
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.setenv("KFS_SANITIZE", "1")
    # Pre-warmup compilations are expected, not violations.
    compile_cache.note_compilation("eng", ("prefill", 1, 16))
    assert sanitizer.violations() == {}
    compile_cache.declare_warmup_complete("eng")
    compile_cache.note_compilation("eng", ("prefill", 1, 32))
    assert sanitizer.violations() == {"recompile": 1}
    pinned = recorder.dump(10)["pinned"]
    assert pinned and pinned[-1]["sanitizer"] == "recompile"
    assert pinned[-1]["source"] == "eng"
    # Another engine still warming is NOT flagged.
    compile_cache.note_compilation("other", ("prefill", 1, 32))
    assert sanitizer.violations() == {"recompile": 1}


def test_forbidden_transfer_counted_pinned_and_reraised(
        monkeypatch, recorder):
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.setenv("KFS_SANITIZE", "1")
    with pytest.raises(Exception, match="[Dd]isallow"):
        with sanitizer.loop_guard("test-loop"):
            jnp.sum(jnp.arange(4) * np.arange(4))  # implicit H2D
    assert sanitizer.violations() == {"forbidden_transfer": 1}
    assert _pinned_reasons(recorder) == \
        ["sanitizer_forbidden_transfer"]


def test_loop_guard_survives_non_lifo_overlap(monkeypatch):
    # Two engines share one server loop and their guard scopes exit
    # in COMPLETION order: the first exit must not disarm the
    # still-running engine, and the last must actually disarm.
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.setenv("KFS_SANITIZE", "1")
    x = jnp.arange(3)
    cm_a = sanitizer.loop_guard("engine-a")
    cm_b = sanitizer.loop_guard("engine-b")
    cm_a.__enter__()
    cm_b.__enter__()
    cm_a.__exit__(None, None, None)   # A drains first (non-LIFO)
    with pytest.raises(Exception, match="[Dd]isallow"):
        float(x[0])                   # B's guard must still be armed
    cm_b.__exit__(None, None, None)
    assert float(x[0]) == 0.0         # fully disarmed, no leak


def test_engine_sanitize_sources_are_never_recycled():
    from kfserving_tpu.engine.buckets import BucketPolicy
    from kfserving_tpu.engine.jax_engine import JaxEngine

    def make():
        e = JaxEngine(lambda p, x: x, {"w": jnp.asarray(1.0)},
                      batch_buckets=BucketPolicy([1]))
        src = e.sanitize_source
        e.close()
        return src

    # Sequential create/close pairs reuse heap addresses; the
    # sanitize identity must be monotonic anyway.
    sources = {make() for _ in range(3)}
    assert len(sources) == 3


def test_sanctioned_fetch_allows_under_guard(monkeypatch):
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.setenv("KFS_SANITIZE", "1")
    with sanitizer.loop_guard("test-loop"):
        with sanitizer.sanctioned_fetch():
            assert float(jnp.arange(3)[1]) == 1.0
    assert sanitizer.violations() == {}


@pytest.mark.asyncio
async def test_loop_stall_watchdog(monkeypatch, recorder):
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.setenv("KFS_SANITIZE", "1")
    wd = sanitizer.LoopStallWatchdog(
        asyncio.get_running_loop(), threshold_ms=80).start()
    try:
        await asyncio.sleep(0.1)     # healthy beats first
        before = wd.stalls           # ~0; a loaded CI box may tick it
        time.sleep(0.4)              # block the loop: one episode
        await asyncio.sleep(0.1)     # let the late beat land
        assert wd.stalls >= before + 1
        assert sanitizer.violations().get("loop_stall", 0) \
            == wd.stalls             # one violation per episode
        entry = recorder.dump(100)["pinned"][-1]
        assert entry["sanitizer"] == "loop_stall"
        assert entry["stall_ms"] >= 80
    finally:
        wd.stop()


# ============================================= sanitizer: generate smoke
@pytest.fixture(scope="module")
def tiny():
    from kfserving_tpu.models.decoder import DecoderLM, decoder_tiny
    cfg = decoder_tiny(num_layers=2, hidden_size=64, num_heads=2,
                       intermediate_size=128, max_seq=MAX_SEQ,
                       vocab_size=96)
    module = DecoderLM(cfg)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return module, variables


def _engine(tiny, **kw):
    from kfserving_tpu.engine.generator import GenerationEngine
    module, variables = tiny
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [8, 16, 32, MAX_SEQ])
    return GenerationEngine(module, variables, **kw)


@pytest.mark.asyncio
async def test_generate_smoke_zero_violations_post_warmup(
        monkeypatch, recorder, tiny):
    """The fast-tier sanitize smoke: warmup traffic, declared warmup,
    then N decode steps under the armed transfer guard — zero
    violations is the acceptance bar."""
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.setenv("KFS_SANITIZE", "1")
    eng = _engine(tiny, name="sanitize-smoke")
    try:
        # Warmup: touch the bucket the steady state uses.
        toks, reason = await eng.complete([5, 9, 2],
                                          max_new_tokens=4)
        assert reason == "length" and len(toks) == 4
        sanitizer.declare_warmup_complete(eng.sanitize_source)
        # N decode steps in the declared shape set.
        for seed_tok in (7, 11, 13):
            toks, reason = await eng.complete(
                [seed_tok, 1, 3], max_new_tokens=6)
            assert reason == "length" and len(toks) == 6
        assert sanitizer.violations() == {}
        assert _pinned_reasons(recorder) == []
    finally:
        await eng.close()


@pytest.mark.asyncio
async def test_generate_injected_recompile_storm_is_caught(
        monkeypatch, recorder, tiny):
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.setenv("KFS_SANITIZE", "1")
    eng = _engine(tiny, name="sanitize-storm")
    try:
        await eng.complete([5, 9, 2], max_new_tokens=2)
        sanitizer.declare_warmup_complete(eng.sanitize_source)
        # A prompt in an un-warmed bucket = a fresh prefill program
        # after declared warmup: the injected recompile.
        await eng.complete(list(range(1, 21)), max_new_tokens=2)
        assert sanitizer.violations() == {"recompile": 1}
        entry = recorder.dump(10)["pinned"][-1]
        assert entry["sanitizer"] == "recompile"
        assert entry["source"].startswith("generator:sanitize-storm:")
    finally:
        await eng.close()
    # Process-monotonic identity: a reloaded engine with the same
    # model name must not inherit this warmup declaration.  (Created
    # after close — engine init does H2D transfers, which the
    # still-armed guard of a live engine on this thread would
    # disallow.)
    reloaded = _engine(tiny, name="sanitize-storm")
    assert reloaded.sanitize_source != eng.sanitize_source
    reloaded.shutdown_nowait()


@pytest.mark.asyncio
async def test_generate_injected_forbidden_transfer_is_caught(
        monkeypatch, recorder, tiny):
    from kfserving_tpu.protocol.errors import InferenceError
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.setenv("KFS_SANITIZE", "1")
    eng = _engine(tiny, name="sanitize-transfer")
    # Inject an implicit transfer INTO the scheduler loop via a hook
    # the pipeline runs every iteration.
    orig = eng._expire_deadlines

    def poisoned():
        float(jnp.arange(3)[0])
        orig()

    eng._expire_deadlines = poisoned
    try:
        with pytest.raises(InferenceError):
            await eng.complete([5, 9, 2], max_new_tokens=4)
        assert sanitizer.violations() == {"forbidden_transfer": 1}
        entry = recorder.dump(10)["pinned"][-1]
        assert entry["sanitizer"] == "forbidden_transfer"
        assert entry["source"] == "sanitize-transfer"
    finally:
        eng.shutdown_nowait()


def test_jax_engine_full_warmup_arms_recompile_assertion(
        monkeypatch, recorder):
    from kfserving_tpu.engine.buckets import BucketPolicy
    from kfserving_tpu.engine.jax_engine import JaxEngine
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.setenv("KFS_SANITIZE", "1")
    engine = JaxEngine(lambda params, x: x * params["w"],
                       {"w": jnp.asarray(2.0)},
                       batch_buckets=BucketPolicy([1, 2]))
    try:
        engine.warmup(np.ones((3,), np.float32))
        assert sanitizer.violations() == {}
        # Within the warmed grid: batch of 2 pads to bucket 2.
        engine.predict_sync(np.ones((2, 3), np.float32))
        assert sanitizer.violations() == {}
    finally:
        engine.close()


def test_jax_engine_minimal_warmup_does_not_arm(monkeypatch):
    from kfserving_tpu.engine.buckets import BucketPolicy
    from kfserving_tpu.engine.jax_engine import JaxEngine
    from kfserving_tpu.reliability import sanitizer
    monkeypatch.setenv("KFS_SANITIZE", "1")
    engine = JaxEngine(lambda params, x: x * params["w"],
                       {"w": jnp.asarray(2.0)},
                       batch_buckets=BucketPolicy([1, 2]))
    try:
        engine.warmup(np.ones((3,), np.float32), minimal=True)
        # Minimal warmup deliberately lazy-loads the rest of the
        # grid: the late compile is the chosen trade, not a
        # violation.
        engine.predict_sync(np.ones((1, 3), np.float32))
        assert sanitizer.violations() == {}
    finally:
        engine.close()


# ======================================================= server wiring
@pytest.mark.asyncio
async def test_server_health_reports_sanitizer_and_pins(monkeypatch):
    from kfserving_tpu.reliability import sanitizer
    from tests.utils import http_json, running_server
    monkeypatch.setenv("KFS_SANITIZE", "1")
    # Generous stall threshold: a loaded CI box must not trip the
    # watchdog and pollute the exact violation assertions below.
    monkeypatch.setenv("KFS_SANITIZE_STALL_MS", "10000")
    from kfserving_tpu.model.model import Model

    class _Probe(Model):
        def load(self):
            self.ready = True
            return True

        async def predict(self, request):
            return {"predictions": request["instances"]}

    probe = _Probe("probe")
    probe.load()
    async with running_server([probe]) as server:
        status, body = await http_json(server.http_port, "GET",
                                       "/v2/health/ready")
        assert status == 200
        assert body["sanitizer"]["enabled"] is True
        assert body["sanitizer"]["watchdog"] is True
        assert body["sanitizer"]["violations"] == {}
        # A violation shows up in health, /metrics, and the pinned
        # flight-recorder feed.
        sanitizer.record_violation("recompile", {"source": "t"})
        status, body = await http_json(server.http_port, "GET",
                                       "/v2/health/ready")
        assert body["sanitizer"]["violations"] == {"recompile": 1}
        status, metrics = await http_json(server.http_port, "GET",
                                          "/metrics")
        text = metrics if isinstance(metrics, str) \
            else metrics.decode()
        assert 'kfserving_tpu_sanitizer_violations_total' \
            '{kind="recompile"} 1' in text
        status, fr = await http_json(server.http_port, "GET",
                                     "/debug/flightrecorder?pinned=1")
        assert any(e.get("pinned") == "sanitizer_recompile"
                   for e in fr["pinned"])
    # Server stop tears the watchdog down.
    assert sanitizer.status()["watchdog"] is False


@pytest.mark.asyncio
async def test_server_without_sanitize_has_no_block(monkeypatch):
    from tests.utils import http_json, running_server
    monkeypatch.delenv("KFS_SANITIZE", raising=False)
    from kfserving_tpu.model.model import Model

    class _Probe(Model):
        def load(self):
            self.ready = True
            return True

        async def predict(self, request):
            return {"predictions": request["instances"]}

    probe = _Probe("probe")
    probe.load()
    async with running_server([probe]) as server:
        status, body = await http_json(server.http_port, "GET",
                                       "/v2/health/ready")
        assert status == 200
        assert "sanitizer" not in body
