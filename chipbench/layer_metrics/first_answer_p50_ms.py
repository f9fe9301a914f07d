"""first_answer_p50_ms: from when a request was due to the first byte of its
answer (time to first token), median over the requests due in the window,
below the knee.  Recorded, not judged: each of the 160 requests' times
strays by half a second from run to run (where it falls in the engine's
0.67-s cycle), so the median of a 50-s window spreads by 4-8% over six runs
of one schedule (PERF.md, PR 23, call 8), more than half of the widest bound
there is."""

from chipbench import stats

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "host_clock"
MOVES = "request_mean_ms"


def read(run):
    return stats.first_answer_quantile_ms(run, 0.5)
