"""Traffic file + seed + seconds -> the requests a run offers.

Rule: every seed offers the same work.  A traffic file fixes the multiset of
the requests' lengths (the quantiles (i + 0.5) / n of its two distributions)
and, for an open loop, how many there are.

closed loop: the request list is a sequence of blocks of `block` requests;
    every block holds the same multiset, freshly permuted by the seed.
    Clients pull from the front, so however far a run gets, it has offered
    whole blocks of the same work plus part of one.  The list has no end
    (`closed_stream`): `requests` in the traffic file is the first batch,
    `closed_requests(traffic, seed, requests)`, drawn in one piece as it
    always was, and past it further blocks are drawn one by one as clients
    pull, each from a stream of its own (`closed:<seed>:<block index>`).  A
    count, however large, is a ceiling that a faster server reaches: then
    tokens per second cannot rise and the traced tail of the window holds
    an idle chip (PERF.md, PR 41).
open loop: exactly round(rate * seconds) arrivals fall in the window, uniform
    draws (a Poisson process conditioned on its count), with their own
    quantile multiset; the lead-in before and the tail after the window are
    built the same way from their own counts, so the window's work does not
    depend on them.  The arrival offsets AND which lengths arrive at each
    are drawn from the traffic file's own `arrival_seed`, the same for every
    run seed; the run's seed draws the prompts' characters.  An open loop's
    schedule is its work: with offsets drawn from the run's seed, runs of one
    seed agreed and seeds differed by 7% in time to first answer (PR 23,
    chiprun call 5); with the offsets fixed and only the lengths permuted by
    the seed, whole request times still spread by 4.7% over six seeds, and by
    2.0% once the lengths were fixed too (calls 6 and 8).  A long answer
    behind a bunch of arrivals is other work than a short one there.  (The
    median of time to first answer strays by 4-8% either way: PERF.md.)
"""

import itertools
import json
import random

# The byte tokenizer maps one character to one token and adds BOS; printable
# ASCII keeps the JSON body one byte per token.
_ALPHABET = "abcdefghijklmnopqrstuvwxyz ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789.,"


def load_traffic(path: str) -> dict:
    with open(path) as f:
        traffic = json.load(f)
    if traffic.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    return traffic


def quantile_lengths(dist: dict, n: int) -> list:
    """The n values at quantiles (i + 0.5) / n of `dist`, as whole tokens."""
    lo, hi = float(dist["lo"]), float(dist["hi"])
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "loguniform":
        return [int(round(lo * (hi / lo) ** q)) for q in qs]
    if dist["dist"] == "uniform":
        return [int(round(lo + (hi - lo) * q)) for q in qs]
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def _permuted_pairs(traffic: dict, n: int, rng: random.Random) -> list:
    prompts = quantile_lengths(traffic["prompt_tokens"], n)
    outputs = quantile_lengths(traffic["output_tokens"], n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return list(zip(prompts, outputs))


def prompt_text(tokens: int, rng: random.Random) -> str:
    """`tokens` tokens under the byte tokenizer: BOS plus tokens - 1 chars."""
    return "".join(rng.choices(_ALPHABET, k=max(1, tokens - 1)))


def _request(i: int, pair, rng: random.Random, due_s=None) -> dict:
    prompt_tokens, output_tokens = pair
    return {"i": i, "prompt_tokens": prompt_tokens,
            "output_tokens": output_tokens,
            "prompt": prompt_text(prompt_tokens, rng), "due_s": due_s}


def closed_requests(traffic: dict, seed: int, count: int) -> list:
    """At least `count` requests in whole blocks; no due times."""
    rng = random.Random(f"closed:{seed}")
    block = int(traffic["block"])
    pairs = []
    while len(pairs) < count:
        pairs.extend(_permuted_pairs(traffic, block, rng))
    return [_request(i, pair, rng) for i, pair in enumerate(pairs)]


def closed_stream(traffic: dict, seed: int):
    """The closed loop's requests, without end: the first batch, built here
    and now, then whole blocks for as long as a client asks.  A later
    block's text is drawn as each request is pulled, so the load generator
    never stops for a whole block."""
    first = closed_requests(traffic, seed, int(traffic["requests"]))
    block = int(traffic["block"])
    whole = len(first) // block  # `closed_requests` gives whole blocks

    def later_blocks():
        for index in itertools.count(whole):
            rng = random.Random(f"closed:{seed}:{index}")
            for j, pair in enumerate(_permuted_pairs(traffic, block, rng)):
                yield _request(index * block + j, pair, rng)

    return itertools.chain(first, later_blocks())


def open_requests(traffic: dict, seed: int, seconds: float) -> list:
    """Lead-in, window and tail arrivals, sorted by due time.  Due times are
    seconds from the window's opening; lead-in arrivals are negative."""
    rng = random.Random(f"open:{seed}")
    when = random.Random(f"arrivals:{traffic['arrival_seed']}")
    which = random.Random(f"lengths:{traffic['arrival_seed']}")
    rate = float(traffic["rate_per_s"])
    spans = [(-float(traffic["lead_in_s"]), 0.0),
             (0.0, float(seconds)),
             (float(seconds), float(seconds) + float(traffic["tail_s"]))]
    arrivals = []
    for start, end in spans:
        n = int(round(rate * (end - start)))
        dues = sorted(when.uniform(start, end) for _ in range(n))
        arrivals.extend(zip(dues, _permuted_pairs(traffic, n, which)))
    return [_request(i, pair, rng, due_s=due)
            for i, (due, pair) in enumerate(arrivals)]
