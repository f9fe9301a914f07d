"""request_mean_ms: from when a request was due to the last token of its
answer, mean over the requests whose last token arrived in the window: what
a caller that waits for the whole answer feels, time to first token and the
stream's pace together.  An end-to-end metric only where no standing queue
sets it."""

from chipbench import stats


def read(run):
    values = stats.request_ms(run["records"], run["window"])
    return sum(values) / len(values) if values else None
