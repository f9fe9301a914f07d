"""Operations and bytes of a routed expert layer, computed from its shapes
and from what the routers chose.  Kept with the benchmark, like
`opsbytes.py`, so that the experts' roofline share is computed the same way
before and after a PR changes how they are computed."""


def decode_expert_matmuls(pairs: float, touched: float, tokens: int,
                          hidden: int, width: int, bytes_per_value: int):
    """One layer-step of decode: `tokens` rows, each through its own
    experts (`pairs` (token, expert) pairs in all), `touched` distinct
    experts among them.

    Returns (floating-point operations, bytes moved to or from HBM).  The
    operations are the routed ones: gate, up and down projections of every
    pair, 2 per multiply-add.  The bytes are each touched expert's three
    [hidden, width] matrices read once, plus the layer's input read and its
    output written per token; an expert no row chose need not be read, and
    the intermediate activations need not leave the chip.
    """
    matrix = hidden * width
    flops = 2 * 3 * pairs * matrix
    nbytes = (touched * 3 * matrix + 2 * tokens * hidden) * bytes_per_value
    return flops, nbytes
