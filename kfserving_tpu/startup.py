"""Startup phase tracking: where a replica's boot time actually goes.

The r4 soak measured overlapped successors taking 35-55 s to load with
no breakdown (VERDICT r4 weak #4) — this module makes every boot
self-reporting.  Phases are measured from PROCESS BIRTH (read from
/proc/self/stat, so the interpreter + import cost that happens before
any of our code runs is visible as the first phase), recorded as
cumulative seconds-since-birth marks, and served by the model server
at GET /startup_phases; the recycling orchestrator attaches them to
its swap_breakdown.
"""

import json
import logging
import os
import time
from typing import Any, Dict, Optional

logger = logging.getLogger("kfserving_tpu.startup")

_marks: Dict[str, float] = {}
_birth: Optional[float] = None
_device: Optional[Dict[str, Any]] = None


def _process_birth_monotonic() -> float:
    """CLOCK_MONOTONIC timestamp of process creation (exec), from
    /proc/self/stat field 22 (starttime, in clock ticks since boot).
    Falls back to import time on non-Linux."""
    try:
        with open("/proc/self/stat", "rb") as f:
            stat = f.read().split(b")")[-1].split()
        ticks = int(stat[19])  # field 22 overall; 20th after comm
        hz = os.sysconf("SC_CLK_TCK")
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        # starttime is relative to boot; CLOCK_MONOTONIC is too.
        return time.monotonic() - (uptime - ticks / hz)
    except Exception:
        return time.monotonic()


def mark(phase: str) -> float:
    """Record `phase` as completed now; returns seconds since process
    birth.  Phases are cumulative timestamps, so consumers diff
    adjacent marks for per-phase durations."""
    global _birth
    if _birth is None:
        _birth = _process_birth_monotonic()
    t = time.monotonic() - _birth
    _marks[phase] = round(t, 3)
    return t


def phases() -> Dict[str, float]:
    return dict(_marks)


# Import time is itself a phase boundary: everything before this point
# (interpreter start, sitecustomize, the importing module's own import
# chain) lands in "interpreter_imports".
mark("interpreter_imports")


def report_device() -> Dict[str, Any]:
    """Name the device(s) this process holds, as JAX reports them.

    The chip-owning servers call this at start-up, BEFORE any model
    loads: the record is logged as one line (so whoever started the
    process can refuse a wrong platform in seconds) and served under
    "device" by the server metadata route (GET /v2)."""
    import jax

    from kfserving_tpu.engine.hbm import device_hbm_bytes
    from kfserving_tpu.engine.jax_engine import device_peak_flops
    from kfserving_tpu.observability.profiling.roofline import (
        device_peak_hbm_bw,
    )

    from kfserving_tpu.engine import compile_cache

    global _device
    compile_cache.count_jax_compile_events()
    devices = jax.devices()
    _device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "ids": [d.id for d in devices],
        # Device ids are process-local (a process pinned to one chip of
        # a four-chip host sees id 0): the pin itself tells replicas
        # sharing a host apart (control/topology.py).
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "hbm_bytes": [device_hbm_bytes(d) for d in devices],
        "peak_flops": device_peak_flops(),
        "peak_hbm_bw": device_peak_hbm_bw(),
    }
    logger.warning("device %s", json.dumps(_device))
    return _device


def device() -> Optional[Dict[str, Any]]:
    """The start-up device record plus each device's HBM in use now
    and the most it has held since start (None where the backend
    reports none); None in a process that never reported one (CPU
    frameworks)."""
    if _device is None:
        return None
    import jax

    from kfserving_tpu.engine.hbm import device_hbm_stat

    devices = jax.devices()
    return {**_device,
            "hbm_in_use": [device_hbm_stat("bytes_in_use", d)
                           for d in devices],
            "hbm_peak": [device_hbm_stat("peak_bytes_in_use", d)
                         for d in devices]}


def exit_with_parent() -> None:
    """From here on this process is sent SIGTERM when the process that
    started it ends, however that one ended (Linux's PR_SET_PDEATHSIG; a
    no-op elsewhere): for a replica under a harness or a supervisor that
    does not reap its children, where a server that outlives a killed
    parent keeps the chip.  A parent that is gone already ends it now."""
    import ctypes
    import signal

    try:
        libc = ctypes.CDLL("libc.so.6", use_errno=True)
    except OSError:
        return
    libc.prctl(1, signal.SIGTERM)  # PR_SET_PDEATHSIG
    if os.getppid() == 1:
        os.kill(os.getpid(), signal.SIGTERM)
