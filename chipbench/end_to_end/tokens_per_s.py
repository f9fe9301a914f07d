"""tokens_per_s: output tokens streamed per second, counted by each token's
arrival time inside the window, over all requests, whether or not they
completed there.  What a chip costs the operator of a saturated replica."""

from chipbench import stats


def read(run):
    window = run["window"]
    return stats.tokens_in_window(run["records"], window) / (
        window[1] - window[0])
