"""Live roofline telemetry: engine FLOP / bandwidth / goodput gauges.

The engines have carried FLOP and padding-waste accounting in their
`stats()` dicts since PR 0 (jax_engine's cost-model MFU) and this PR
(the generator's decode/prefill FLOP, KV-working-set bandwidth, and
goodput accounting) — but stats dicts are an offline artifact: bench
scripts read them after the run.  This module *promotes* them into
process-registry gauges at `/metrics` scrape time, so the running
server continuously exposes the numbers ROADMAP item 1 derives
offline, federated through the router under a `replica` label like
every PR-2 series:

    kfserving_tpu_engine_mfu{model,phase}         achieved/peak FLOP/s
    kfserving_tpu_engine_achieved_tflops{model,phase}
    kfserving_tpu_engine_padding_waste_ratio{model,bucket}
    kfserving_tpu_engine_goodput_ratio{model}     useful tokens over
                                                  useful + garbage-wave
    kfserving_tpu_engine_hbm_bw_util_ratio{model} decode KV+param read
                                                  rate over peak HBM BW

`publish_gauges` consumes the stat keys it owns and returns them, so
the server's generic engine-stats exporter (server/app.py `_metrics`)
never double-declares the same family under a second registry.

Peak HBM bandwidth mirrors jax_engine.device_peak_flops: a per-chip
table with a `KFS_PEAK_HBM_BW` override (bytes/s), returning None on
unknown backends so the utilization gauge is omitted rather than
faked.
"""

import logging
import os
from typing import Any, Dict, Optional, Set

from kfserving_tpu.observability import metrics as obs

logger = logging.getLogger("kfserving_tpu.profiling.roofline")


def device_peak_hbm_bw() -> Optional[float]:
    """Peak HBM bandwidth (bytes/s) of the serving chip, for the
    decode bandwidth-utilization gauge.  Override with
    KFS_PEAK_HBM_BW; None when unknown (CPU backend)."""
    env = os.getenv("KFS_PEAK_HBM_BW")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for marker, bw in (("v5 lite", 819e9), ("v5e", 819e9),
                       ("v5p", 2765e9), ("v6", 1640e9),
                       ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9)):
        if marker in kind:
            return bw
    return None


def _clamp01(value: float) -> float:
    return min(1.0, max(0.0, float(value)))


# stats() keys this module owns, per phase, mapped onto the gauge
# families above.  (key, phase) for the MFU/TFLOPs pairs.
_MFU_KEYS = (("mfu", "infer"), ("decode_mfu", "decode"),
             ("prefill_mfu", "prefill"))
_TFLOPS_KEYS = (("achieved_tflops", "infer"),
                ("achieved_decode_tflops", "decode"),
                ("achieved_prefill_tflops", "prefill"))
_WASTE_KEYS = ("bucket_pad_waste", "prefill_bucket_pad_waste")


def publish_gauges(model: str, stats: Dict[str, Any]) -> Set[str]:
    """Publish an engine stats dict's roofline numbers as registry
    gauges labeled by model.  Returns the stat keys consumed (the
    caller's generic per-key exporter must skip them — the same
    family declared from two registries would abort strict scrapes).
    Never raises into the scrape path."""
    consumed: Set[str] = set()
    try:
        for key, phase in _MFU_KEYS:
            value = stats.get(key)
            if isinstance(value, (int, float)):
                obs.engine_mfu().labels(
                    model=model, phase=phase).set(float(value))
                consumed.add(key)
        for key, phase in _TFLOPS_KEYS:
            value = stats.get(key)
            if isinstance(value, (int, float)):
                obs.engine_achieved_tflops().labels(
                    model=model, phase=phase).set(float(value))
                consumed.add(key)
        for key in _WASTE_KEYS:
            waste = stats.get(key)
            if isinstance(waste, dict):
                for bucket, value in waste.items():
                    if isinstance(value, (int, float)):
                        obs.engine_padding_waste_ratio().labels(
                            model=model, bucket=str(bucket)).set(
                                _clamp01(value))
                consumed.add(key)
        value = stats.get("goodput_ratio")
        if isinstance(value, (int, float)):
            obs.engine_goodput_ratio().labels(model=model).set(
                _clamp01(value))
            consumed.add("goodput_ratio")
        value = stats.get("hbm_bw_util")
        if isinstance(value, (int, float)):
            obs.engine_hbm_bw_util_ratio().labels(model=model).set(
                _clamp01(value))
            consumed.add("hbm_bw_util")
    except Exception:  # telemetry must never fail a scrape
        logger.exception("roofline gauge publish failed for %s", model)
    return consumed
