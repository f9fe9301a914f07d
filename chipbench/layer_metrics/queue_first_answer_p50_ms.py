"""queue_first_answer_p50_ms: median time from due to first token where
callers outnumber slots.  By Little's law it is queue depth over throughput,
so it is recorded beside tokens_per_s and never judged."""

from chipbench import stats

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "host_clock"
MOVES = "tokens_per_s"


def read(run):
    return stats.first_answer_quantile_ms(run, 0.5)
