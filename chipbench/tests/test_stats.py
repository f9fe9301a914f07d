"""The metric arithmetic on hand-made records."""

import math

from chipbench import stats


def record(i, due, first, token_times, ok=True, prompt=10, sent=None):
    return {"i": i, "phase": "window", "prompt_tokens": prompt,
            "output_tokens": len(token_times), "due": due,
            "sent": due if sent is None else sent, "first": first,
            "last": token_times[-1] if token_times else None,
            "tokens": token_times, "ok": ok, "error": None if ok else "x"}


def test_percentile_is_nearest_rank():
    values = sorted(range(1, 101))
    assert stats.percentile(values, 0.5) == 50
    assert stats.percentile(values, 0.9) == 90
    assert stats.percentile(values, 0.99) == 99
    assert stats.percentile([7], 0.9) == 7
    assert math.isnan(stats.percentile([], 0.5))


def test_tokens_count_by_arrival_not_by_completion():
    window = (10.0, 20.0)
    records = [
        record(0, 5.0, 6.0, [6.0, 9.0, 10.0, 11.0]),     # began before
        record(1, 12.0, 13.0, [13.0, 19.999, 20.0, 25.0]),  # ends after
        record(2, 30.0, 31.0, [31.0]),
    ]
    assert stats.tokens_in_window(records, window) == 4
    assert stats.tokens_in_window(records, (10.0, 15.0)) == 3


def test_first_answer_is_from_due_over_requests_due_in_the_window():
    window = (10.0, 20.0)
    records = [
        record(0, 9.0, 9.5, [9.5]),               # due before: not counted
        record(1, 10.0, 10.25, [10.25], sent=10.001),
        record(2, 19.0, 21.0, [21.0], sent=19.004),  # answered after close
        record(3, 19.5, None, [], ok=False),      # never answered
    ]
    assert stats.first_answer_ms(records, window) == [250.0, 2000.0]
    late = stats.lateness_ms(records, window)
    assert [round(v, 3) for v in late] == [0.0, 1.0, 4.0]
    assert len(stats.due_in_window(records, window)) == 3


def test_tpot_over_requests_that_end_in_the_window():
    window = (0.0, 10.0)
    records = [
        record(0, 0.0, 1.0, [1.0, 1.5, 2.0]),          # 0.5 s a token
        record(1, 0.0, 1.0, [1.0, 3.0, 12.0]),         # ends after: left out
        record(2, 0.0, 2.0, [2.0]),                    # one token: no gap
        record(3, 0.0, 1.0, [1.0, 1.1], ok=False),     # failed: left out
    ]
    assert stats.tpot_ms(records, window) == [500.0]
    # a request's whole time runs from due to last token, same population
    # (the one-token answer counts; it was due at 0 and ended at 2)
    assert stats.request_ms(records, window) == [2000.0, 2000.0]
    assert stats.request_ms(records, (0.0, 13.0)) == [2000.0, 2000.0, 12000.0]


def test_live_context_is_time_weighted():
    # one request, prompt 10: holds 11 tokens of context for 2 s, 12 for 2 s
    records = [record(0, 0.0, 1.0, [1.0, 3.0, 5.0], prompt=10)]
    assert stats.live_context_tokens(records, (1.0, 5.0)) == 11.5
    assert stats.live_context_tokens(records, (0.0, 10.0)) == 4.6
    assert stats.live_context_tokens(records, (3.0, 5.0)) == 12.0
