"""Operations and bytes of what a hybrid (state-space + expert-parallel)
model adds to a decode step, computed from shapes and from what the routers
chose.  Kept with the benchmark, like `opsbytes.py` and `opsbytes_moe.py`,
so that a roofline share is computed the same way before and after a PR
changes how the work is done."""


def decode_plain_expert_matmuls(pairs: float, touched: float, tokens: int,
                                hidden: int, width: int,
                                bytes_per_value: int):
    """One expert layer-step of decode over the experts this chip holds:
    `tokens` rows, `pairs` (token, expert) pairs routed to held experts,
    `touched` distinct held experts among them.  An expert is not gated:
    down(relu(up·x)²), two [hidden, width] matrices, `width` the published
    one whatever padding the matrices are stored with.

    Returns (floating-point operations, bytes moved to or from HBM): the up
    and down projections of every pair, 2 per multiply-add; each touched
    expert's two matrices read once, plus the layer's input read and its
    output written per token.
    """
    matrix = hidden * width
    flops = 2 * 2 * pairs * matrix
    nbytes = (touched * 2 * matrix + 2 * tokens * hidden) * bytes_per_value
    return flops, nbytes


def decode_ssm_scan(sequences: int, heads: int, head_dim: int, state: int,
                    groups: int, state_bytes: int = 4):
    """One Mamba-2 layer-step of decode between the convolution and the
    gate: S <- exp(Δ·A)·S + Δ·x ⊗ B, y = S·C + D·x for every sequence.

    Returns (floating-point operations, bytes moved to or from HBM): per
    state element one multiply by the decay, one multiply-add of the outer
    product and one multiply-add of the read-out (5 operations); the state
    [heads, head_dim, state] read once and written once, plus x, Δ, B, C in
    and y out (4 bytes each, small beside the state).
    """
    elements = sequences * heads * head_dim * state
    flops = 5 * elements
    small = sequences * (2 * heads * head_dim + heads + 2 * groups * state)
    nbytes = 2 * elements * state_bytes + 4 * small
    return flops, nbytes
