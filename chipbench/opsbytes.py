"""Operations and bytes a kernel's call needs, computed from its shapes.
Kept with the benchmark, so that a kernel's roofline share is computed the
same way before and after a PR changes the kernel."""


def paged_decode_attention(context_tokens: float, sequences: int, heads: int,
                           head_dim: int, bytes_per_value: int):
    """One layer's decode attention for a batch: every sequence has one
    query row and reads the keys and values of its own context.

    context_tokens: summed context length over the batch's live sequences.
    Returns (floating-point operations, bytes moved to or from HBM): q.k and
    p.v are 2 operations per key or value element each; the bytes are each
    context row of K and of V read once, plus the query read and the output
    written per sequence.  Block tables and lengths are not counted.
    """
    kv_elements = context_tokens * heads * head_dim
    flops = 2 * 2 * kv_elements
    nbytes = (2 * kv_elements + 2 * sequences * heads * head_dim) \
        * bytes_per_value
    return flops, nbytes
