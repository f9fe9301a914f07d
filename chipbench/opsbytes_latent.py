"""Operations and bytes of decode attention over a LATENT cache
(models/deepseek_v3.py): a layer keeps one row a token, `rank` + `rope`
numbers, which every query head reads whole as its key and over its first
`rank` numbers as its value.  Kept with the benchmark, like `opsbytes.py`, so
that the share computed from it is computed the same way before and after a
PR changes the kernel or the pool's layout: a row counts `rank` + `rope`
numbers whatever the pool pads it to, so padding shows as lost share and not
as extra work."""


def latent_decode_attention(rows: float, sequences: int, heads: int,
                            rank: int, rope: int, bytes_per_value: int):
    """One layer-step of absorbed decode attention: every sequence has one
    query row of `heads` heads, each `rank` + `rope` wide, and reads `rows`
    latent rows (summed over the batch).

    Returns (floating-point operations, bytes moved to or from HBM): q.k is
    2 operations per query head and row element, p.v 2 per query head and
    value element (the row's first `rank`); the bytes are each row read
    ONCE (it is key and value), plus the query read and the answer written
    per sequence.  Tables, lengths, the walk and the up-projections that
    absorb W_kvb are not counted."""
    flops = 2 * rows * heads * ((rank + rope) + rank)
    nbytes = (rows * (rank + rope)
              + sequences * heads * ((rank + rope) + rank)) * bytes_per_value
    return flops, nbytes
