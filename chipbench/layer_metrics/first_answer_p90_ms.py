"""first_answer_p90_ms: from due to first token, 90th percentile over the
requests due in the window (16 of 160 lie beyond it).  Recorded beside the
median, not judged: in full sets the p90 spread by up to 19% (PERF.md,
PR 23, calls 6 and 8)."""

from chipbench import stats

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "host_clock"
MOVES = "request_mean_ms"


def read(run):
    return stats.first_answer_quantile_ms(run, 0.9)
