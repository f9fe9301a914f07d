"""generator_late_p99_ms: send time - due time, 99th percentile over the
requests due in the window, by the load generator's own clock.  A starved
generator must not be read as a fast server."""

from chipbench import stats

UNIT, LAYER, SOURCE = "ms", "load generator", "host_clock"
MOVES = "request_mean_ms"


def read(run):
    late = stats.lateness_ms(run["records"], run["window"])
    return stats.percentile(late, 0.99) if late else None
