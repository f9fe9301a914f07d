"""tpot_p50_ms: median over requests of (last token - first token) /
(tokens - 1), over the requests whose last token arrived in the window: the
reading speed of one stream."""

from chipbench import stats


def read(run):
    values = stats.tpot_ms(run["records"], run["window"])
    return stats.percentile(values, 0.5) if values else None
