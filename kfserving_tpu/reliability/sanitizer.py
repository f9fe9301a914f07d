"""Runtime device-discipline sanitizer (``KFS_SANITIZE=1``).

The static device tier (kfslint's ``host-sync`` /
``jit-recompile-hazard`` rules) proves the *code* can't express the
two silent MFU killers; this module proves the *process* doesn't
commit them at runtime — the dynamic twin, for the paths static
analysis can't see (dynamic dispatch, third-party callbacks, shapes
computed at runtime):

- **transfer guard** — while a generation scheduler loop runs,
  ``jax.transfer_guard("disallow")`` is armed on the loop thread
  (`loop_guard`).  Any implicit host<->device transfer inside a
  decode wave raises, is counted as a ``forbidden_transfer``
  violation, pinned into the flight recorder, and re-raised (a
  sanitize run fails loudly, never quietly).  The sanctioned fetch
  points (`_fetch_wave`, the engine's result fetch) wrap themselves
  in `sanctioned_fetch()` — an explicit ``transfer_guard("allow")``
  scope — mirroring their static ``host-sync`` pragmas.
- **recompile-after-warmup** — engines report every
  first-dispatch-per-shape through
  ``engine/compile_cache.note_compilation``.  Once a source declares
  its warmup complete (`declare_warmup_complete`), any further
  compilation from that source is a ``recompile`` violation: the
  bucket grid was supposed to be closed, and a post-warmup compile is
  a recompile storm's first drop.
- **event-loop stall watchdog** — the process heartbeat
  (``observability/profiling/heartbeat.py``, the thread the engines'
  stall watch runs on too) posts ``call_soon_threadsafe`` ticks at
  the configured loop; a tick the loop fails to run within
  ``KFS_SANITIZE_STALL_MS`` (default 250) is a ``loop_stall``
  violation with the observed stall attached.

Violations land in ``kfserving_tpu_sanitizer_violations_total{kind}``
and, when a flight recorder is attached (the server wires its
monitoring recorder in), as pinned ``sanitizer_<kind>`` entries —
evidence that survives the healthy traffic after the incident.

``KFS_SANITIZE`` unset/0 is a true no-op: every hook degrades to a
dict lookup or a null context manager, jax is never imported from
here, and nothing here watches a loop.
"""

import contextlib
import os
import threading
from typing import Any, Dict, Optional

ENV_VAR = "KFS_SANITIZE"
STALL_ENV_VAR = "KFS_SANITIZE_STALL_MS"
DEFAULT_STALL_MS = 250.0

VIOLATION_KINDS = ("forbidden_transfer", "recompile", "loop_stall")


def enabled() -> bool:
    return os.environ.get(ENV_VAR, "") not in ("", "0", "false")


class _State:
    def __init__(self):
        self.lock = threading.Lock()
        self.violations: Dict[str, int] = {}
        self.warm: set = set()          # sources past declared warmup
        self.recorder = None            # FlightRecorder or None
        self.watchdog: Optional["LoopStallWatchdog"] = None


_state = _State()


def reset() -> None:
    """Tests only: drop violation counts, warmup declarations, the
    recorder attachment, and any running watchdog."""
    stop_watchdog()
    with _state.lock:
        _state.violations.clear()
        _state.warm.clear()
        _state.recorder = None


def attach_flight_recorder(recorder) -> None:
    """Pin future violations into `recorder` (the owning server
    attaches its monitoring FlightRecorder at startup and detaches
    with None on stop — a dead server's buffer has no debug surface
    and must not be kept alive by this global)."""
    _state.recorder = recorder


def record_violation(kind: str, detail: Dict[str, Any]) -> None:
    """Count + pin one violation.  Public so tests and the watchdog
    share one path; production code reaches it via the hooks."""
    with _state.lock:
        _state.violations[kind] = _state.violations.get(kind, 0) + 1
    from kfserving_tpu.observability import metrics as obs

    obs.sanitizer_violations_total().labels(kind=kind).inc()
    recorder = _state.recorder
    if recorder is not None:
        entry = {"sanitizer": kind}
        entry.update(detail)
        recorder.record(entry, pin=f"sanitizer_{kind}")


def violations() -> Dict[str, int]:
    with _state.lock:
        return dict(_state.violations)


def status() -> Dict[str, Any]:
    """The health-endpoint block: enabled flag, armed sources, and
    per-kind violation counts (all zero is the clean bill)."""
    with _state.lock:
        return {
            "enabled": enabled(),
            "stall_threshold_ms": _stall_threshold_ms(),
            "watchdog": _state.watchdog is not None,
            "warmed_sources": sorted(_state.warm),
            "violations": dict(_state.violations),
        }


# -- recompile-after-warmup --------------------------------------------------

def declare_warmup_complete(source: str) -> None:
    """After this, any compilation noted for `source` is a violation.
    Engines call it at the end of warmup(); harnesses call it once
    their declared warmup traffic has run."""
    if not enabled():
        return
    with _state.lock:
        _state.warm.add(source)


def note_compilation(source: str, key: Any) -> None:
    """Called (via engine/compile_cache.note_compilation) on every
    first-dispatch-per-shape.  Post-warmup notes are violations."""
    if not enabled():
        return
    with _state.lock:
        armed = source in _state.warm
    if armed:
        record_violation("recompile", {
            "source": source,
            "shape": str(key),
            "detail": "compilation after declared warmup — the "
                      "bucket grid was supposed to be closed",
        })


# -- transfer guard ----------------------------------------------------------

def _is_transfer_guard_error(exc: BaseException) -> bool:
    msg = str(exc).lower()
    return "disallow" in msg and "transfer" in msg


# Per-thread guard arming.  Two engines sharing one server loop both
# hold loop_guard across awaits, and their scopes exit in COMPLETION
# order, not LIFO — nesting two jax.transfer_guard context managers
# would let the first exit restore the pre-guard state under the
# still-running engine (disarming it) and the last exit leak
# "disallow" onto the loop forever.  Instead one underlying jax
# context manager per thread, entered at depth 0->1 and exited at
# 1->0; intermediate exits only decrement, so the guard stays armed
# exactly while any loop_guard scope is live.
_guard_tls = threading.local()


def _guard_enter() -> None:
    depth = getattr(_guard_tls, "depth", 0)
    if depth == 0:
        import jax

        cm = jax.transfer_guard("disallow")
        cm.__enter__()
        _guard_tls.cm = cm
    _guard_tls.depth = depth + 1


def _guard_exit() -> None:
    _guard_tls.depth -= 1
    if _guard_tls.depth == 0:
        cm = _guard_tls.cm
        _guard_tls.cm = None
        cm.__exit__(None, None, None)


@contextlib.contextmanager
def loop_guard(source: str = "scheduler"):
    """Arm ``jax.transfer_guard("disallow")`` for the enclosed scope
    (the generation scheduler wraps its pipeline in this, so the
    guard covers the loop thread for the engine's lifetime).  A
    disallowed transfer is counted+pinned, then re-raised."""
    if not enabled():
        yield
        return
    _guard_enter()
    try:
        yield
    except Exception as exc:
        if _is_transfer_guard_error(exc):
            record_violation("forbidden_transfer", {
                "source": source,
                "error": str(exc)[:300],
            })
        raise
    finally:
        _guard_exit()


@contextlib.contextmanager
def sanctioned_fetch():
    """The explicit-allow scope for the declared fetch points — the
    runtime twin of their line-tight ``host-sync`` pragmas.  Null
    when sanitizing is off (the production hot path pays one env
    read)."""
    if not enabled():
        yield
        return
    import jax

    with jax.transfer_guard("allow"):
        yield


# -- event-loop stall watchdog -----------------------------------------------

def _stall_threshold_ms() -> float:
    try:
        return float(os.environ.get(STALL_ENV_VAR,
                                    DEFAULT_STALL_MS))
    except ValueError:
        return DEFAULT_STALL_MS


class LoopStallWatchdog:
    """A subscriber of the process heartbeat: told, eight times a
    second, how late the watched loop ran the tick posted to it, or
    for how long the tick that is out has waited.  Past the threshold
    that is one ``loop_stall`` violation per stall episode, recorded
    while the loop is still held — the dynamic counterpart of
    kfslint's ``spin-loop``/``async-blocking``."""

    def __init__(self, loop, threshold_ms: Optional[float] = None):
        self.loop = loop
        self.threshold_ms = (threshold_ms if threshold_ms is not None
                             else _stall_threshold_ms())
        self._stalled = False  # one violation per episode
        self._watch = None
        self.stalls = 0

    def start(self) -> "LoopStallWatchdog":
        from kfserving_tpu.observability.profiling import HEARTBEAT

        self._watch = HEARTBEAT.watch(self.loop, held=self._held)
        return self

    def stop(self) -> None:
        from kfserving_tpu.observability.profiling import HEARTBEAT

        watch, self._watch = self._watch, None
        HEARTBEAT.unwatch(watch)

    def _held(self, ms: float) -> None:
        # Runs on the heartbeat thread.
        if ms <= self.threshold_ms:
            self._stalled = False
        elif not self._stalled:
            self._stalled = True
            self.stalls += 1
            record_violation("loop_stall", {
                "stall_ms": round(ms, 1),
                "threshold_ms": round(self.threshold_ms, 1),
            })


def start_watchdog(loop) -> Optional[LoopStallWatchdog]:
    """Start (at most one) stall watchdog on `loop` when sanitizing.
    Returns the watchdog, or None when disabled/already running."""
    if not enabled():
        return None
    with _state.lock:
        if _state.watchdog is not None:
            return None
        wd = LoopStallWatchdog(loop)
        _state.watchdog = wd
    return wd.start()


def stop_watchdog() -> None:
    with _state.lock:
        wd, _state.watchdog = _state.watchdog, None
    if wd is not None:
        wd.stop()
