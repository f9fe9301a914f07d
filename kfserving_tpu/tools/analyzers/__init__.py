"""kfslint — AST-based concurrency & serving-discipline analyzer.

Usage (CLI)::

    python -m kfserving_tpu.tools.analyzers [paths ...]
    kfs-lint [paths ...]                      # console-script alias

With no paths it analyzes the installed ``kfserving_tpu`` package.
Exit 0 means: zero findings that are neither pragma-suppressed nor in
the committed baseline, AND zero stale baseline entries.

Rules (see ``asyncrules.py`` / ``discipline.py`` / ``devicerules.py``
for the defect class each one encodes): the concurrency four
(``async-blocking``, ``spin-loop``, ``await-under-lock``,
``cancellation-safety``), the serving-discipline pair
(``fault-site``, ``metric-name``), and the XLA/JAX device tier
(``host-sync``, ``jit-recompile-hazard``, ``blocking-dispatch``,
``prng-key-reuse``).

Suppression: ``# kfslint: disable=<rule>[,<rule>]  <justification>``
on the finding's line.  Known legacy findings live in
``baseline.json`` next to this package; a baseline entry whose
finding disappeared fails the run as stale.
"""

import os
from typing import List

from kfserving_tpu.tools.analyzers.asyncrules import (
    AsyncBlockingRule,
    AwaitUnderLockRule,
    CancellationSafetyRule,
    SpinLoopRule,
)
from kfserving_tpu.tools.analyzers.core import (
    Finding,
    Rule,
    analyze_paths,
    analyze_snippets,
    analyze_source,
    apply_baseline,
    load_baseline,
    save_baseline,
)
from kfserving_tpu.tools.analyzers.devicerules import (
    BlockingDispatchRule,
    HostSyncRule,
    JitRecompileHazardRule,
    PrngKeyReuseRule,
)
from kfserving_tpu.tools.analyzers.discipline import (
    FaultSiteRule,
    MetricNameRule,
)

__all__ = [
    "Finding", "Rule", "analyze_paths", "analyze_snippets",
    "analyze_source", "apply_baseline", "load_baseline",
    "save_baseline", "default_rules", "rule_ids",
    "default_baseline_path", "default_target", "default_targets",
]


def default_rules() -> List[Rule]:
    """Fresh rule instances (rules carry per-run state; never share
    instances across runs)."""
    return [AsyncBlockingRule(), SpinLoopRule(), AwaitUnderLockRule(),
            CancellationSafetyRule(), FaultSiteRule(),
            MetricNameRule(), HostSyncRule(),
            JitRecompileHazardRule(), BlockingDispatchRule(),
            PrngKeyReuseRule()]


def rule_ids() -> List[str]:
    return [r.id for r in default_rules()]


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(__file__), "baseline.json")


def default_target() -> str:
    """The installed package root — what a bare `kfs-lint` analyzes."""
    import kfserving_tpu
    return os.path.dirname(os.path.abspath(kfserving_tpu.__file__))


def default_targets() -> List[str]:
    """Everything a bare `kfs-lint` (and the fast-tier gate) scans:
    the package tree plus the `tests/` tree living next to it when
    present — tests run the same event-loop/device disciplines the
    package does, and a spin-loop in a test hangs CI exactly like one
    in the scheduler would."""
    pkg = default_target()
    roots = [pkg]
    repo = os.path.dirname(pkg)
    # Only a repo checkout carries its pyproject next to the package;
    # in site-packages a sibling `tests/` dir is some OTHER
    # distribution's packaging accident, not ours to lint.
    if os.path.isfile(os.path.join(repo, "pyproject.toml")):
        tests = os.path.join(repo, "tests")
        if os.path.isdir(tests):
            roots.append(tests)
    return roots
