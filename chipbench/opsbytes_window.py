"""Operations and bytes of decode attention in a model whose layers are of
two kinds: sliding-window layers, which read the `window` latest rows of a
sequence's K and V, beside layers that read its whole context; and how many
rows that is, from the load generator's own records.  Kept with the
benchmark, like `opsbytes.py`, so that the shares computed from it are
computed the same way before and after a PR changes the kernel.
`opsbytes.paged_decode_attention` counts every context row in every layer
and one K/V head a query head; neither holds here."""


def live_rows(records, window, cap=None) -> float:
    """Time-weighted mean, over the span `window`, of the summed K (or V)
    rows one layer reads for the requests that hold a decode slot: a
    request between its first and its last token has a context of its
    prompt and the tokens streamed so far, of which a layer reads
    min(context, cap) rows (`cap` None: all of them, which is
    `stats.live_context_tokens`)."""
    if window[1] <= window[0]:
        return float("nan")
    total = 0.0
    for r in records:
        times = r["tokens"]
        for n, (t0, t1) in enumerate(zip(times, times[1:]), start=1):
            lo, hi = max(t0, window[0]), min(t1, window[1])
            if hi > lo:
                context = r["prompt_tokens"] + n
                total += (context if cap is None
                          else min(context, cap)) * (hi - lo)
    return total / (window[1] - window[0])


def grouped_decode_attention(rows: float, sequences: int, query_heads: int,
                             kv_heads: int, head_dim: int,
                             bytes_per_value: int):
    """One layer-step of decode attention under grouped-query attention:
    every sequence has one query row of `query_heads` heads and reads
    `rows` rows (summed over the batch) of K and of V, `kv_heads` heads
    each.

    Returns (floating-point operations, bytes moved to or from HBM): q.k
    and p.v are 2 operations per query head and key or value element each;
    the bytes are each row of K and of V read once, plus the query read and
    the output written per sequence.  Tables, lengths and the walk are not
    counted."""
    flops = 2 * 2 * rows * query_heads * head_dim
    nbytes = (2 * rows * kv_heads * head_dim
              + 2 * sequences * query_heads * head_dim) * bytes_per_value
    return flops, nbytes
