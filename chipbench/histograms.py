"""Reading what `prom.py` does not: a counter summed over its label sets,
and which buckets of a histogram grew between two scrapes.

A histogram's `_bucket` lines are cumulative (`le` is an upper bound and
each line counts everything at or under it), so a bucket's own count is
its line less the line below, and a bucket grew between two scrapes where
that difference rose.  The highest bucket that grew bounds the largest
observation of the interval from above: a maximum read from counters,
with the resolution of the program's buckets.
"""

import math
import re

LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def samples(text: str, name: str):
    """(labels, value) of every sample of exactly `name`."""
    for line in text.splitlines():
        if not line.startswith(name) or line[len(name):len(name) + 1] \
                not in ("{", " "):
            continue
        head, _, value = line.rpartition(" ")
        yield dict(LABEL.findall(head[len(name):])), float(value)


def summed(text: str, name: str, **labels):
    """`name` summed over the label sets that carry `labels`; None where
    the exposition has no such sample."""
    values = [v for have, v in samples(text, name)
              if all(have.get(k) == str(w) for k, w in labels.items())]
    return sum(values) if values else None


def delta_summed(scrapes: dict, first: str, last: str, name: str, **labels):
    """How far `name`, summed over its label sets, moved between two named
    scrapes.  None where the last scrape has no such sample (a server
    without the series); a label set born in between counts from 0."""
    if first not in scrapes or last not in scrapes:
        return None
    b = summed(scrapes[last]["metrics"], name, **labels)
    if b is None:
        return None
    return b - (summed(scrapes[first]["metrics"], name, **labels) or 0.0)


def bucket_counts(text: str, histogram: str, **labels) -> dict:
    """{other labels: {upper bound: the bucket's own count}} of one
    histogram, over the children that carry `labels`."""
    cumulative = {}
    for have, value in samples(text, histogram + "_bucket"):
        if any(have.get(k) != str(w) for k, w in labels.items()):
            continue
        le = have.pop("le")
        bound = math.inf if le == "+Inf" else float(le)
        cumulative.setdefault(tuple(sorted(have.items())), {})[bound] = value
    own = {}
    for child, lines in cumulative.items():
        below = 0.0
        own[child] = {}
        for bound in sorted(lines):
            own[child][bound] = lines[bound] - below
            below = lines[bound]
    return own


def grown_upper_bound(scrapes: dict, first: str, last: str, histogram: str,
                      **labels):
    """The upper bound of the highest bucket of `histogram`, over the
    children that carry `labels`, whose own count grew between two named
    scrapes.  The `+Inf` bucket reads as the last finite bound: at least
    that.  None where the last scrape has no such histogram, or nothing
    was observed in between."""
    if first not in scrapes or last not in scrapes:
        return None
    before = bucket_counts(scrapes[first]["metrics"], histogram, **labels)
    after = bucket_counts(scrapes[last]["metrics"], histogram, **labels)
    highest = None
    for child, buckets in after.items():
        finite = [b for b in buckets if b != math.inf]
        for bound, count in buckets.items():
            if count > before.get(child, {}).get(bound, 0.0):
                bound = min(bound, max(finite, default=0.0))
                highest = bound if highest is None else max(highest, bound)
    return highest
