"""hbm_in_use_gb: device memory in use as the server reports it under /v2,
median of the samples taken each second of the window.  Says whether the
parameters are resident beside the KV pool."""

from chipbench import stats

UNIT, LAYER, SOURCE = "GB", "device", "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    samples = [max(s[1]) for s in run["device_samples"] if all(s[1])]
    return stats.median(samples) / 1e9 if samples else None
