"""ops/ssm.py against the recurrence written as a loop over tokens:

    S_t = exp(Δ_t·A)·S_{t-1} + Δ_t·x_t ⊗ B_t,   y_t = S_t·C_t + D·x_t

Both sides are float32 on the CPU and differ by the order of their sums: a
few 1e-6 on outputs of magnitude 1 to 10.  The tolerance, 1e-4, is far under
what any missing term moves (dropping the decay between two chunks, or the
state a chunk enters with, moves outputs by 1e-1 and more).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfserving_tpu.ops import ssm

TOL = 1e-4
H, P, G, N, CHUNK, K = 4, 6, 2, 8, 16, 4


def inputs(batch, length, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (batch, length, H, P))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, length, H)) - 1)
    a = -jnp.exp(jax.random.uniform(keys[2], (H,), minval=0.0, maxval=2.5))
    b = jax.random.normal(keys[3], (batch, length, G, N))
    c = jax.random.normal(keys[4], (batch, length, G, N))
    d = jax.random.normal(keys[5], (H,))
    return x, dt, a, b, c, d


def sequential(x, dt, a, b, c, d, lengths=None):
    """(y [B, L, H, P], the state after each row's last real token)."""
    x, dt, a, b, c, d = (np.asarray(t, np.float64)
                         for t in (x, dt, a, b, c, d))
    batch, length = x.shape[:2]
    b, c = (np.repeat(t, H // G, axis=2) for t in (b, c))
    y = np.zeros_like(x)
    states = np.zeros((batch, H, P, N))
    for r in range(batch):
        s = np.zeros((H, P, N))
        for t in range(length if lengths is None else int(lengths[r])):
            s = np.exp(dt[r, t] * a)[:, None, None] * s + (
                dt[r, t][:, None] * x[r, t])[:, :, None] * b[r, t][:, None]
            y[r, t] = np.einsum("hpn,hn->hp", s, c[r, t]) \
                + d[:, None] * x[r, t]
        states[r] = s
    return y, states


@pytest.mark.parametrize("length", [1, 5, 16, 37, 64, 77])
def test_the_chunked_scan_is_the_sequential_recurrence(length):
    """Lengths that are and are not multiples of the chunk (16)."""
    args = inputs(2, length, seed=length)
    want_y, want_s = sequential(*args)
    y, s = ssm.ssd_prefill(*args, chunk=CHUNK)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=TOL, rtol=0)


@pytest.mark.parametrize("lengths", [(40, 9), (16, 33), (1, 48)])
def test_padded_rows_leave_the_state_of_the_last_real_token(lengths):
    args = inputs(2, 48, seed=3)
    want_y, want_s = sequential(*args, lengths=lengths)
    y, s = ssm.ssd_prefill(*args, lengths=jnp.asarray(lengths), chunk=CHUNK)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=TOL, rtol=0)
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(y)[r, :n], want_y[r, :n],
                                   atol=TOL, rtol=0)


@pytest.mark.parametrize("split", [1, 21, 32])
def test_prefill_then_steps_is_one_long_scan(split):
    x, dt, a, b, c, d = inputs(3, 40, seed=9)
    want_y, want_s = ssm.ssd_prefill(x, dt, a, b, c, d, chunk=CHUNK)
    y, s = ssm.ssd_prefill(x[:, :split], dt[:, :split], a, b[:, :split],
                           c[:, :split], d, chunk=CHUNK)
    steps = [y]
    for t in range(split, 40):
        y_t, s = ssm.ssd_step(s, x[:, t], dt[:, t], a, b[:, t], c[:, t], d)
        steps.append(y_t[:, None])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(steps, axis=1)),
                               np.asarray(want_y), atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=TOL,
                               rtol=0)


def conv_inputs(batch, length, channels=10):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    return (jax.random.normal(keys[0], (batch, length, channels)),
            jax.random.normal(keys[1], (channels, K)),
            jax.random.normal(keys[2], (channels,)))


def test_the_convolution_is_causal_and_starts_from_zeros():
    xbc, w, bias = conv_inputs(2, 12)
    got, state = ssm.causal_conv(xbc, w, bias)
    x, wn = np.asarray(xbc), np.asarray(w)
    padded = np.concatenate([np.zeros((2, K - 1, 10)), x], axis=1)
    want = sum(padded[:, j:j + 12] * wn[:, j] for j in range(K)) \
        + np.asarray(bias)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.nn.silu(want)), atol=1e-5,
                               rtol=0)
    np.testing.assert_array_equal(np.asarray(state), x[:, -(K - 1):])


@pytest.mark.parametrize("lengths", [(12, 12), (7, 2), (1, 3)])
def test_conv_steps_continue_from_each_rows_own_length(lengths):
    """The window a row's first decode step reads is the K-1 rows before
    its own length (zeros where the sequence is shorter), not the
    bucket's last rows."""
    xbc, w, bias = conv_inputs(2, 12)
    _, state = ssm.causal_conv(xbc, w, bias, jnp.asarray(lengths))
    more = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 10))
    for r, n in enumerate(lengths):
        whole = jnp.concatenate([xbc[r:r + 1, :n], more[r:r + 1]], axis=1)
        want, _ = ssm.causal_conv(whole, w, bias)
        row_state = state[r:r + 1]
        for t in range(3):
            got, row_state = ssm.conv_step(more[r:r + 1, t], row_state, w,
                                           bias)
            np.testing.assert_allclose(np.asarray(got[0]),
                                       np.asarray(want[0, n + t]),
                                       atol=1e-5, rtol=0)


@pytest.mark.parametrize("rows, buckets, served", [
    (8, [1024], True), (1, [1024], True), (4, (1024,), True),
    (None, [1024], False), (0, [1024], False), (9, [1024], False),
    (8, [512], True), (8, [1024, 2048], False),
    (3, [512], True), (8, [512, 1024], True), (16, [512], False),
    (8, [256], False),
])
def test_the_prefill_shapes_that_have_run_on_the_chip(rows, buckets, served):
    reason = ssm.unproven_on_chip(rows, buckets)
    assert (reason is None) == served


# -- a row that carries several prompts, each from a chunk boundary --------------
BLOCK = CHUNK
# name -> (the bucket, [(the prompt's tokens, the block it starts at)]),
# the first of them the prompt that is looked at.
PACKED = {
    "alone": (64, [(37, 0)]),
    "first": (64, [(20, 0), (9, 2), (16, 3)]),
    "last": (64, [(23, 2), (32, 0)]),
    "between": (96, [(21, 1), (7, 0), (33, 3)]),
    "one-block": (64, [(16, 2), (32, 0), (5, 3)]),
    "the-bucket": (64, [(64, 0)]),
    "shorter-than-the-taps": (64, [(2, 1), (16, 0), (1, 2), (3, 3)]),
    "a-chunk-multiple": (64, [(32, 1), (11, 0), (16, 3)]),
    "not-a-chunk-multiple": (80, [(45, 1), (3, 0), (1, 4)]),
}


def _laid(bucket, prompts):
    """(segments [1, bucket], positions [1, bucket], last [1, blocks]) of
    `prompts` as (tokens, first block) in one row."""
    segments = np.full((1, bucket), -1, np.int32)
    positions = np.zeros((1, bucket), np.int32)
    last = np.zeros((1, bucket // BLOCK), np.int32)
    for n, block in prompts:
        start = block * BLOCK
        assert (segments[0, start:start + n] == -1).all()
        segments[0, start:start + n] = block
        positions[0, start:start + n] = np.arange(n)
        last[0, block] = start + n - 1
    return jnp.asarray(segments), jnp.asarray(positions), jnp.asarray(last)


@pytest.mark.parametrize("case", sorted(PACKED))
def test_a_packed_prompts_scan_is_its_scan_alone_from_a_zero_state(case):
    """y over the prompt's positions and the state it leaves (the one
    after the chunk its last token lies in, read at the chunk it starts
    at), for every prompt of the row, against the prompt alone in a row
    with `lengths`."""
    bucket, prompts = PACKED[case]
    x, dt, a, b, c, d = inputs(1, bucket, seed=len(case))
    segments, positions, last = _laid(bucket, prompts)
    y, states = ssm.ssd_prefill(x, dt, a, b, c, d, chunk=CHUNK,
                                packed=(segments, positions))
    assert states.shape == (1, -(-bucket // CHUNK), H, P, N)
    assert y.dtype == states.dtype == jnp.float32
    for n, block in prompts:
        at = slice(block * BLOCK, block * BLOCK + n)
        want_y, want_s = ssm.ssd_prefill(
            x[:, at], dt[:, at], a, b[:, at], c[:, at], d, chunk=CHUNK)
        np.testing.assert_allclose(np.asarray(y[:, at]), np.asarray(want_y),
                                   atol=1e-5, rtol=0)
        # read at any chunk of the prompt, its first among them
        for chunk in (block * BLOCK // CHUNK, int(last[0, block]) // CHUNK):
            np.testing.assert_allclose(np.asarray(states[0, chunk]),
                                       np.asarray(want_s[0]), atol=1e-5,
                                       rtol=0)


@pytest.mark.parametrize("case", sorted(PACKED))
def test_a_packed_prompts_convolution_is_its_convolution_alone(case):
    """The activations over the prompt's positions and the K-1 rows
    before its own end (zeros where it is shorter), entry for entry."""
    bucket, prompts = PACKED[case]
    xbc, w, bias = conv_inputs(1, bucket)
    _, positions, last = _laid(bucket, prompts)
    got, rows = ssm.causal_conv(xbc, w, bias, packed=(positions, last))
    assert rows.shape == (bucket // BLOCK, K - 1, 10)
    assert rows.dtype == xbc.dtype
    for n, block in prompts:
        at = slice(block * BLOCK, block * BLOCK + n)
        want, want_rows = ssm.causal_conv(xbc[:, at], w, bias)
        np.testing.assert_allclose(np.asarray(got[:, at]), np.asarray(want),
                                   atol=1e-5, rtol=0)
        padded = jnp.pad(want_rows, ((0, 0), (max(0, K - 1 - n), 0), (0, 0)))
        np.testing.assert_allclose(np.asarray(rows[block]),
                                   np.asarray(padded[0, -(K - 1):]),
                                   atol=1e-5, rtol=0)


def test_rows_of_a_packed_batch_are_laid_independently():
    """Two rows, other prompts in each: a row is what it is alone."""
    x, dt, a, b, c, d = inputs(2, 64, seed=21)
    laid = [_laid(64, PACKED[name][1]) for name in ("first", "last")]
    segments, positions, _ = (jnp.concatenate(t) for t in zip(*laid))
    y, states = ssm.ssd_prefill(x, dt, a, b, c, d, chunk=CHUNK,
                                packed=(segments, positions))
    for r, (seg, pos, _) in enumerate(laid):
        want_y, want_s = ssm.ssd_prefill(
            x[r:r + 1], dt[r:r + 1], a, b[r:r + 1], c[r:r + 1], d,
            chunk=CHUNK, packed=(seg, pos))
        np.testing.assert_allclose(np.asarray(y[r]), np.asarray(want_y[0]),
                                   atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(states[r]),
                                   np.asarray(want_s[0]), atol=1e-5, rtol=0)
