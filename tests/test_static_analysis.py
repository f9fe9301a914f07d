"""kfslint (ISSUE 11): the AST concurrency & serving-discipline
analyzer.

Layout:

- golden fixtures: every rule is proven by a firing fixture (each
  expected finding line carries a `# FIRE` marker the test reads
  back) AND a non-firing fixture (zero findings of any rule);
- edge cases: nested async defs, asyncio- vs threading-lock
  classification, pragma placement/scoping, baseline staleness;
- the fast-tier gate: the live `kfserving_tpu` tree is clean modulo
  the committed baseline (this is the CI entry next to the
  check_metrics smoke — keep it under the 5 s budget);
- regressions for the real defects this PR fixed (control-plane
  blocking file I/O on the event loop): the fixed modules stay
  kfslint-clean, and the offloaded paths still behave.
"""

import json
import os
import subprocess
import sys

import pytest

from kfserving_tpu.tools import analyzers
from kfserving_tpu.tools.analyzers import naming
from kfserving_tpu.tools.analyzers.__main__ import main as kfslint_main
from kfserving_tpu.tools.analyzers.core import (
    analyze_snippets,
    analyze_source,
    apply_baseline,
    pragma_lines,
)
from kfserving_tpu.tools.analyzers.discipline import (
    FaultSiteRule,
    render_manifest,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures",
                        "kfslint")
REPO_PKG = analyzers.default_target()

RULE_FIXTURES = [
    ("async-blocking", "async_blocking"),
    ("spin-loop", "spin_loop"),
    ("await-under-lock", "await_under_lock"),
    ("cancellation-safety", "cancellation"),
    ("fault-site", "fault_site"),
    ("metric-name", "metric_name"),
    # Device tier (ISSUE 14) — see tests/test_device_discipline.py
    # for the per-rule edge cases; the golden contract lives here
    # with the others.
    ("host-sync", "host_sync"),
    ("jit-recompile-hazard", "jit_recompile"),
    ("blocking-dispatch", "blocking_dispatch"),
    ("prng-key-reuse", "prng_reuse"),
]


def _analyze(path):
    return analyzers.analyze_paths([path], analyzers.default_rules())


def _fire_lines(path):
    with open(path) as f:
        return {i for i, line in enumerate(f, start=1)
                if "# FIRE" in line}


# ------------------------------------------------- golden fixtures
@pytest.mark.parametrize("rule,stem", RULE_FIXTURES)
def test_rule_fires_exactly_on_golden_fixture(rule, stem):
    path = os.path.join(FIXTURES, f"{stem}_fire.py")
    fire = _fire_lines(path)
    assert fire, f"{path} has no FIRE markers"
    lines = {f.line for f in _analyze(path) if f.rule == rule}
    assert lines == fire


@pytest.mark.parametrize("rule,stem", RULE_FIXTURES)
def test_rule_silent_on_clean_fixture(rule, stem):
    path = os.path.join(FIXTURES, f"{stem}_clean.py")
    findings = _analyze(path)
    assert findings == [], [f.render() for f in findings]


# ------------------------------------------------- rule edge cases
def test_nested_async_def_inside_sync_function_is_checked():
    src = (
        "import time\n"
        "def factory():\n"
        "    async def worker():\n"
        "        time.sleep(1)\n"
        "    return worker\n")
    findings = analyze_source(src, "x.py", analyzers.default_rules())
    assert [f.rule for f in findings] == ["async-blocking"]
    assert findings[0].line == 4


def test_sync_def_nested_in_async_def_is_not_the_async_frame():
    src = (
        "import time\n"
        "async def handler(loop):\n"
        "    def blocking_helper():\n"
        "        time.sleep(1)\n"
        "    return await loop.run_in_executor(None, blocking_helper)\n")
    assert analyze_source(src, "x.py", analyzers.default_rules()) == []


def test_asyncio_lock_allowed_threading_lock_flagged_under_with():
    src = (
        "import asyncio, threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._alock = asyncio.Lock()\n"
        "        self._tlock = threading.Lock()\n"
        "    async def a(self):\n"
        "        with self._alock:\n"
        "            await self.f()\n"
        "    async def b(self):\n"
        "        with self._tlock:\n"
        "            await self.f()\n")
    findings = analyze_source(src, "x.py", analyzers.default_rules())
    assert [(f.rule, f.line) for f in findings] == \
        [("await-under-lock", 10)]


def test_spin_loop_needs_async_context_and_no_await():
    src = (
        "import asyncio\n"
        "async def ok(engine):\n"
        "    while engine.hold:\n"
        "        await asyncio.sleep(0)\n"
        "async def bad(engine):\n"
        "    while engine.hold:\n"
        "        engine.poll()\n")
    findings = analyze_source(src, "x.py", analyzers.default_rules())
    assert [(f.rule, f.line) for f in findings] == [("spin-loop", 6)]


def test_cancellation_protected_by_enclosing_try():
    src = (
        "async def f(pool):\n"
        "    try:\n"
        "        conn = await pool.acquire()\n"
        "        await conn.use()\n"
        "    finally:\n"
        "        pool.release()\n")
    assert analyze_source(src, "x.py", analyzers.default_rules()) == []


def test_blocking_helper_needs_unique_name():
    # Two defs share the helper's name: the interprocedural pass must
    # refuse to guess, so only the unique-name variant is flagged.
    ambiguous = (
        "def fetch():\n"
        "    return open('/tmp/x')\n"
        "class Other:\n"
        "    def fetch(self):\n"
        "        return 1\n"
        "async def h(c):\n"
        "    return c.fetch()\n")
    assert analyze_snippets({"x.py": ambiguous},
                            analyzers.default_rules()) == []
    unique = (
        "def read_cfg():\n"
        "    return open('/tmp/x')\n"
        "def relay():\n"
        "    return read_cfg()\n"
        "async def h():\n"
        "    return relay()\n")
    findings = analyze_snippets({"x.py": unique},
                                analyzers.default_rules())
    # Fixpoint: relay() is blocking because read_cfg() is.
    assert [(f.rule, f.line) for f in findings] == \
        [("async-blocking", 6)]


# ------------------------------------------------- pragma semantics
def test_pragma_trailing_and_standalone_placement():
    src = (
        "import time\n"
        "async def f():\n"
        "    time.sleep(1)  # kfslint: disable=async-blocking — why\n"
        "    # kfslint: disable=async-blocking — heads a comment\n"
        "    # block wrapping onto a second line.\n"
        "    time.sleep(2)\n")
    assert analyze_source(src, "x.py", analyzers.default_rules()) == []
    assert pragma_lines(src) == {3: {"async-blocking"},
                                 6: {"async-blocking"}}


def test_pragma_scoping_is_line_tight():
    # A pragma with intervening code does NOT blanket the function.
    src = (
        "import time\n"
        "async def f():\n"
        "    # kfslint: disable=async-blocking — only the next line\n"
        "    time.sleep(1)\n"
        "    time.sleep(2)\n")
    findings = analyze_source(src, "x.py", analyzers.default_rules())
    assert [(f.rule, f.line) for f in findings] == \
        [("async-blocking", 5)]


def test_pragma_suppresses_only_named_rules():
    src = (
        "import time\n"
        "async def f():\n"
        "    time.sleep(1)  # kfslint: disable=spin-loop — wrong rule\n")
    findings = analyze_source(src, "x.py", analyzers.default_rules())
    assert [f.rule for f in findings] == ["async-blocking"]


def test_pragma_inside_string_literal_is_inert():
    src = (
        "import time\n"
        "async def f():\n"
        "    s = '# kfslint: disable=async-blocking'\n"
        "    time.sleep(1)\n")
    findings = analyze_source(src, "x.py", analyzers.default_rules())
    assert [f.rule for f in findings] == ["async-blocking"]


# ------------------------------------------------- baseline
def _finding(rule="spin-loop", path="a.py", line=3, snippet="while x:"):
    from kfserving_tpu.tools.analyzers.core import Finding
    return Finding(rule=rule, path=path, line=line, message="m",
                   snippet=snippet)


def test_baseline_match_consumes_and_ignores_line_churn():
    f = _finding(line=99)  # line moved since the baseline was taken
    baseline = [{"rule": "spin-loop", "path": "a.py", "line": 3,
                 "snippet": "while x:"}]
    new, stale = apply_baseline([f], baseline)
    assert new == [] and stale == []


def test_baseline_entry_budget_is_one_finding_each():
    f1, f2 = _finding(line=3), _finding(line=30)
    baseline = [{"rule": "spin-loop", "path": "a.py",
                 "snippet": "while x:"}]
    new, stale = apply_baseline([f1, f2], baseline)
    assert len(new) == 1 and stale == []


def test_stale_baseline_entry_is_detected():
    baseline = [{"rule": "spin-loop", "path": "a.py",
                 "snippet": "while gone:"}]
    new, stale = apply_baseline([], baseline)
    assert new == [] and stale == baseline


def test_stale_baseline_fails_the_cli_run(tmp_path, capsys):
    stale = tmp_path / "baseline.json"
    stale.write_text(json.dumps([
        {"rule": "spin-loop",
         "path": os.path.join(FIXTURES, "spin_loop_clean.py"),
         "snippet": "while nothing_matches_this:"}]))
    rc = kfslint_main([os.path.join(FIXTURES, "spin_loop_clean.py"),
                       "--baseline", str(stale)])
    assert rc == 1
    assert "stale baseline entry" in capsys.readouterr().out


def test_write_baseline_then_clean_run(tmp_path, capsys):
    fire = os.path.join(FIXTURES, "spin_loop_fire.py")
    bl = tmp_path / "baseline.json"
    assert kfslint_main([fire, "--baseline", str(bl),
                         "--write-baseline"]) == 0
    capsys.readouterr()
    assert kfslint_main([fire, "--baseline", str(bl)]) == 0
    assert "clean" in capsys.readouterr().out


def test_finding_paths_cwd_independent_inside_checkout(
        tmp_path, monkeypatch):
    # The committed baseline keys on repo-root-relative paths
    # ('tests/...'); a bare `kfs-lint` run from ANY cwd must
    # produce the same identities or the baseline false-fails.
    target = os.path.abspath(
        os.path.join(FIXTURES, "spin_loop_fire.py"))
    at_root = {f.path for f in _analyze(target)}
    monkeypatch.chdir(tmp_path)
    elsewhere = {f.path for f in _analyze(target)}
    assert at_root == elsewhere \
        == {"tests/fixtures/kfslint/spin_loop_fire.py"}


def test_finding_paths_invocation_independent():
    # Absolute and relative spellings of the same target must agree
    # on finding paths, or a committed baseline never matches CI.
    rel = os.path.relpath(os.path.join(FIXTURES, "spin_loop_fire.py"))
    abs_ = os.path.abspath(rel)
    assert {f.path for f in _analyze(rel)} \
        == {f.path for f in _analyze(abs_)} \
        == {rel.replace(os.sep, "/")}


def test_lockish_heuristic_requires_whole_segment():
    src = (
        "async def f(pool):\n"
        "    with pool.block_table:\n"   # 'block' is not 'lock'
        "        await pool.grow()\n"
        "    with pool.chain_lock:\n"
        "        await pool.grow()\n")
    findings = analyze_source(src, "x.py", analyzers.default_rules())
    assert [(f.rule, f.line) for f in findings] == \
        [("await-under-lock", 4)]


# ------------------------------------------------- fault-site manifest
def test_manifest_is_its_own_render():
    from kfserving_tpu.reliability import fault_sites
    with open(fault_sites.__file__) as f:
        committed = f.read()
    assert committed == render_manifest(), \
        "fault_sites.py drifted from its generator — run " \
        "python -m kfserving_tpu.tools.analyzers --write-fault-sites"


def test_manifest_render_survives_hostile_descriptions():
    import ast as ast_mod
    rendered = render_manifest({
        "EMPTY_DESC": ("a.b", ""),
        "QUOTED": ("c.d", 'says "hi" \\ there'),
    })
    tree = ast_mod.parse(rendered)  # must stay importable
    ns = {}
    exec(compile(tree, "<manifest>", "exec"), ns)
    assert ns["EMPTY_DESC"] == "a.b" and ns["QUOTED"] == "c.d"
    assert ns["SITES"]["QUOTED"][1] == 'says "hi" \\ there'


def test_manifest_constants_match_sites_table():
    from kfserving_tpu.reliability import fault_sites
    for const, site in fault_sites.site_values().items():
        assert getattr(fault_sites, const) == site


def test_fault_site_rule_flags_dead_manifest_rows():
    rule = FaultSiteRule()
    user = (
        "from kfserving_tpu.reliability.faults import faults\n"
        "async def f(m):\n"
        "    await faults.inject('dataplane.infer', key=m)\n")
    analyze_source(user, "kfserving_tpu/server/dataplane.py", [rule])
    analyze_source("SITES = {}\n",
                   "kfserving_tpu/reliability/fault_sites.py", [rule])
    from kfserving_tpu.reliability import fault_sites

    dead = {f.snippet for f in rule.finalize()}
    assert "DATAPLANE_INFER" not in dead
    # Every manifest row except the one with a live inject call above
    # must be flagged dead — sized off the live manifest so adding a
    # site doesn't silently shrink the rule's coverage.
    assert "ROUTER_DISPATCH" in dead
    assert len(dead) == len(fault_sites.SITES) - 1


def test_fault_site_coverage_skipped_without_manifest_in_scan():
    rule = FaultSiteRule()
    analyze_source("x = 1\n", "some/file.py", [rule])
    assert list(rule.finalize()) == []


# ------------------------------------------------- shared naming rules
def test_naming_rules_shared_with_check_metrics():
    from kfserving_tpu.tools.check_metrics import lint_families
    fams = {"kfserving_tpu_good_total": "counter",
            "kfserving_tpu_bad": "counter",
            "kfserving_tpu_worse_total": "gauge",
            "unprefixed_ms": "histogram",
            "kfserving_tpu_wait_milliseconds": "histogram"}
    runtime = lint_families(fams)
    static = [p for name, kind in sorted(fams.items())
              for p in naming.family_name_problems(name, kind)]
    assert runtime == static and len(runtime) == 5


# ------------------------------------------------- the fast-tier gate
def test_live_tree_is_clean_modulo_baseline():
    # Full default scope: package + tests/; the baseline is empty.
    findings = analyzers.analyze_paths(analyzers.default_targets(),
                                       analyzers.default_rules())
    baseline = analyzers.load_baseline(
        analyzers.default_baseline_path())
    new, stale = apply_baseline(findings, baseline)
    assert new == [], "kfslint findings:\n" + "\n".join(
        f.render() for f in new)
    assert stale == [], f"stale baseline entries: {stale}"


def test_default_targets_cover_package_and_tests():
    targets = analyzers.default_targets()
    names = {os.path.basename(t) for t in targets}
    assert {"kfserving_tpu", "tests"} == names
    # The golden fixtures fire by design and must be pruned from the
    # directory walk (their tests analyze them file-by-file).
    from kfserving_tpu.tools.analyzers.core import iter_python_files
    scanned = list(iter_python_files(targets))
    assert not any("fixtures" in p for p in scanned)
    assert any(p.endswith("test_static_analysis.py") for p in scanned)


@pytest.mark.slow
def test_cli_module_invocation():
    # The acceptance command, end to end in a subprocess.
    proc = subprocess.run(
        [sys.executable, "-m", "kfserving_tpu.tools.analyzers",
         os.path.join(FIXTURES, "spin_loop_fire.py"), "--no-baseline"],
        capture_output=True, text=True)
    assert proc.returncode == 1
    assert "[spin-loop]" in proc.stdout


def test_nonexistent_path_errors_instead_of_passing_clean(capsys):
    rc = kfslint_main(["no/such/dir", "--no-baseline"])
    assert rc == 2
    assert "no such file" in capsys.readouterr().err


def test_cli_list_rules(capsys):
    assert kfslint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule, _stem in RULE_FIXTURES:
        assert rule in out


# --------------------------------------- regressions: fixed defects
# ISSUE 11 satellite: real findings the analyzer surfaced in control/
# (and friends), fixed in this PR.  The static half pins each module
# kfslint-clean; the functional half proves the offloaded paths still
# do their job.

@pytest.mark.parametrize("rel", [
    "control/api.py",          # credential persist blocked the loop
    "control/manager.py",      # apply_files read specs on the loop
    "control/controller.py",   # shard configs written on the loop
    "agent/watcher.py",        # config polls read on the loop
    "client/client.py",        # SDK read key files on callers' loops
    "client/cli.py",           # payload/stdin reads on the loop
])
def test_fixed_modules_stay_kfslint_clean(rel):
    path = os.path.join(REPO_PKG, rel)
    findings = [f for f in _analyze(path)
                if f.rule == "async-blocking"]
    assert findings == [], [f.render() for f in findings]


@pytest.mark.asyncio
async def test_api_credential_persist_offloaded_and_atomic(tmp_path):
    from kfserving_tpu.control.api import ControlAPI
    from kfserving_tpu.server.http import Request
    from kfserving_tpu.storage.credentials import CredentialStore

    store = CredentialStore()
    path = tmp_path / "creds.json"
    api = ControlAPI(controller=None, credentials=store,
                     credentials_path=str(path))
    body = json.dumps({"type": "s3",
                       "data": {"accessKeyId": "AK",
                                "secretAccessKey": "SK"},
                       "serviceAccount": "sa"}).encode()
    resp = await api._create_secret(
        Request("POST", "/v1/secrets", {}, {}, body))
    assert resp.status == 201
    saved = json.loads(path.read_text())
    assert list(saved["secrets"]) and "sa" in saved["serviceAccounts"]
    # Atomic replace: no leftover tmp file.
    assert not (tmp_path / "creds.json.tmp").exists()


@pytest.mark.asyncio
async def test_controller_shard_config_written_off_loop(tmp_path):
    from kfserving_tpu.control.controller import Controller

    class _Strategy:
        def models_on(self, shard):
            return []

    ctl = Controller(orchestrator=None, modelconfig_dir=str(tmp_path))
    await ctl._write_shard_config("svc", "default", _Strategy(), 0)
    cfg = tmp_path / "default-svc-shard-0.json"
    assert json.loads(cfg.read_text()) == []


@pytest.mark.asyncio
async def test_manager_apply_files_reads_via_executor(tmp_path):
    from kfserving_tpu.control.manager import ServingManager

    spec = {"name": "demo",
            "predictor": {"framework": "jax",
                          "storage_uri": "file:///tmp/x"}}
    spec_file = tmp_path / "isvc.json"
    spec_file.write_text(json.dumps(spec))

    applied = []

    class _Ctl:
        async def apply(self, isvc):
            applied.append(isvc)

            class _S:
                ready = True
            return _S()

    stub = type("M", (), {"controller": _Ctl()})()
    await ServingManager.apply_files(stub, [str(spec_file)])
    assert len(applied) == 1 and applied[0].name == "demo"
