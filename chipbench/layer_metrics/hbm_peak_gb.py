"""hbm_peak_gb: the most device memory the served process has held since
its start (`peak_bytes_in_use`, under /v2 as `hbm_peak`), read after the
window: the running decode program's temporaries included, which the
once-a-second samples of hbm_in_use_gb miss.  Says whether resident
parameters would still fit."""

UNIT, LAYER, SOURCE = "GB", "device", "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    peaks = [p for p in run["device_after"].get("hbm_peak") or [] if p]
    return max(peaks) / 1e9 if peaks else None
