"""Mamba-2's mixer between its two projections: the short causal
convolution and the selective state-space recurrence, for a whole prompt
(`causal_conv`, `ssd_prefill`) and for one token a row (`conv_step`,
`ssd_step`).

Per head h of P channels, with group g = h // (H / G) giving its B and C
rows of N state columns, step size Δ_t[h] > 0 and decay rate A[h] < 0:

    S_t[h] = exp(Δ_t[h]·A[h]) · S_{t-1}[h] + Δ_t[h] · x_t[h] ⊗ B_t[g]
    y_t[h] = S_t[h] · C_t[g] + D[h] · x_t[h]

`S[h]` is [P, N] and is kept in float32 whatever the activations are.

`ssd_prefill` computes the same sums in chunks (Dao & Gu 2024, "state
space duality"): inside a chunk of Q tokens every output is a masked
[Q, Q] product of C·Bᵀ and the decays between the two positions; across
chunks one state per chunk is carried by a short recurrence, unrolled.  It is
plain XLA, float32, with the matmuls at `Precision.HIGHEST` (a float32
dot on the TPU otherwise rounds its inputs to bfloat16, and the state is
what the configuration states as float32): a few GFLOP a layer at a
thousand tokens, nothing beside the experts.  A row's tokens past its
`lengths` get Δ = 0: decay 1, input 0, so the state passes them unchanged
and the final state is the one after the row's last real token.

A row may carry several prompts, each from a chunk boundary (`packed`: the
engine's packed prefill, engine/programs.py `prefill_fn`).  Nothing inside
a chunk then knows of prompts beyond what it knows of padding; the two sums
that cross a boundary start again there: the state that enters a chunk at
which a prompt starts is zero, and a tap of the convolution that reaches
back before its prompt's first token reads a zero.  What a prompt leaves is
read where it ends: the state after the chunk its last token lies in, which
the padding behind it passes on unchanged to where the next prompt starts
or the row ends, and the K-1 rows before its own end.

Scopes (`jax.named_scope`): `ssm.conv`, `ssm.scan`; the model puts its two
projections under `ssm.in_proj` and `ssm.out`.
"""

from typing import Optional

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST

# (rows, bucket) of every prefill that `ssd_prefill` has run in on a v5e,
# each beside the recurrence it ran (heads x head size x state, groups).
# The chip hung at (4, 1024) while the chunk recurrence below was a
# `lax.scan`, and why is not known (PERF.md, PR 31, N6): a hang takes the
# chip and raises nothing, so on a TPU the engine refuses at load whatever
# was not run (`unproven_on_chip`), until the cause is found.  A shape is
# added here by whoever has run it there, alone, with a timeout.
# Since PR 55 the programs behind these pairs carry several prompts a row
# (`packed`), and each of the eight was run again as the engine builds it
# (`engine/programs.build`'s `prefill_fn` at the benchmark's two
# configurations, parameters seeded on the device), alone in a child under
# a timeout, five calls with rows of 2 to 5 prompts and five with a lone
# prompt a row: no stall, and a prompt's K/V, state and conv rows bit for
# bit what it leaves alone in a row (PERF.md §6 PR 55 has the times).
CHIP_PROVEN = frozenset(
    # 64 x 64 x 128, 8 groups (PR 31; 3 rows of it too, which no dispatch
    # has: the engine pads a group's rows to a power of two; PR 55, packed,
    # with Nemotron-H's experts)
    [(rows, 1024) for rows in (1, 2, 4, 8)]
    # 32 x 128 x 256, 2 groups (PR 51; PR 55, packed)
    + [(rows, 512) for rows in (1, 2, 4, 8)])


def unproven_on_chip(prefill_rows: Optional[int], buckets) -> Optional[str]:
    """Why a prefill of up to `prefill_rows` rows over `buckets` may not be
    dispatched to a TPU, or None where every shape of it (the powers of two
    that hold up to `prefill_rows` rows, at every bucket) has been run."""
    if not prefill_rows:
        return "prefill_rows must be set"
    rows = [1 << i for i in range((int(prefill_rows) - 1).bit_length() + 1)]
    missing = sorted({(r, int(b)) for r in rows for b in buckets}
                     - CHIP_PROVEN)
    if missing:
        return (f"(rows, bucket) {missing} have not; those that have: "
                f"{sorted(CHIP_PROVEN)}")
    return None


def causal_conv(xbc, weight, bias, lengths: Optional[jax.Array] = None,
                packed=None):
    """Depthwise causal convolution and silu over a sequence that starts
    here (zeros before it).  xbc [B, L, C]; weight [C, K]; bias [C].
    Returns (activated [B, L, C] in xbc's dtype, the last K-1
    pre-activation rows before each row's `lengths` [B, K-1, C]: what
    `conv_step` continues from).

    `packed` (in place of `lengths`) is (`positions` [B, L], from 0 again
    in each prompt of a row; `last` [B, P], the column of a prompt's last
    token, an entry a place a prompt could start): a prompt's first K-1
    tokens read zeros before it, not its neighbour's rows, and the rows
    come back a prompt, [B * P, K-1, C], zeros where the prompt is
    shorter than K-1 (an entry no prompt starts at gives rows nobody
    reads)."""
    bsz, l, ch = xbc.shape
    k = weight.shape[1]
    with jax.named_scope("ssm.conv"):
        window = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        w = weight.astype(jnp.float32)
        taps = [window[:, j:j + l].astype(jnp.float32) for j in range(k)]
        if packed is not None:
            positions, last = packed
            # Tap j of position t is row t - (K-1-j): of its prompt where
            # the prompt has that many tokens before t (the last tap is t
            # itself).
            taps = [jnp.where((positions >= k - 1 - j)[:, :, None], tap, 0.0)
                    for j, tap in enumerate(taps[:-1])] + taps[-1:]
        out = bias.astype(jnp.float32) + sum(
            tap * w[:, j] for j, tap in enumerate(taps))
        if packed is None and lengths is None:
            lengths = jnp.full((bsz,), l, jnp.int32)
        # Row t of xbc is row t + K-1 of the window: the K-1 rows that
        # end at lengths - 1 start at window row `lengths`.
        ends = lengths[:, None] if packed is None else last + 1
        at = ends[..., None] + jnp.arange(k - 1)             # [B, P, K-1]
        state = jnp.take_along_axis(
            window, at.reshape(bsz, -1, 1), axis=1).reshape(
                at.shape + (ch,))
        if packed is not None:
            # The row i before a prompt's last is its own where the last
            # token's position is at least i.
            before = (k - 2 - jnp.arange(k - 1))[None, None, :]
            ours = before <= jnp.take_along_axis(positions, last,
                                                 axis=1)[..., None]
            state = jnp.where(ours[..., None], state, 0)
        return (jax.nn.silu(out).astype(xbc.dtype),
                state.reshape(-1, k - 1, ch))


def conv_step(xbc, state, weight, bias):
    """One token a row: xbc [B, C], state [B, K-1, C] (the rows before
    it).  Returns (activated [B, C], the state moved on by one row)."""
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate(
            [state, xbc[:, None, :].astype(state.dtype)], axis=1)
        out = bias.astype(jnp.float32) + jnp.einsum(
            "bkc,ck->bc", window.astype(jnp.float32),
            weight.astype(jnp.float32))
        return jax.nn.silu(out).astype(xbc.dtype), window[:, 1:]


def ssd_prefill(x, dt, a, b, c, d, lengths: Optional[jax.Array] = None,
                chunk: int = 128, packed=None):
    """The recurrence over whole sequences from a zero state.
    x [B, L, H, P]; dt [B, L, H] (Δ, after its softplus); a [H] (< 0);
    b, c [B, L, G, N]; d [H]; lengths optional [B].  Returns
    (y [B, L, H, P] float32, final state [B, H, P, N] float32).  L need
    not be a multiple of `chunk`.

    `packed` (in place of `lengths`) is (`segments` [B, L], the prompt of
    its row a position belongs to, -1 for padding; `positions` [B, L],
    from 0 again in each prompt), every prompt from a chunk boundary: a
    prompt's sums start from a zero state, and the second return is
    [B, chunks, H, P, N] float32, for every chunk the state that the
    prompt it belongs to leaves (read at the chunk a prompt starts at;
    a chunk of padding holds that of the prompt before it)."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    with jax.named_scope("ssm.scan"):
        x, dt, b, c = (t.astype(jnp.float32) for t in (x, dt, b, c))
        starts = None
        if packed is not None:
            segments, positions = packed
            dt = jnp.where((segments >= 0)[:, :, None], dt, 0.0)
            # [B, chunks]: a prompt starts at the chunk's first column.
            starts = (segments[:, ::chunk] >= 0) & (positions[:, ::chunk]
                                                    == 0)
        elif lengths is not None:
            real = jnp.arange(l)[None, :] < lengths[:, None]
            dt = jnp.where(real[:, :, None], dt, 0.0)
        pad = -l % chunk
        if pad:
            x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad))
                                   + ((0, 0),) * (t.ndim - 2))
                           for t in (x, dt, b, c))
        nc = (l + pad) // chunk
        # Heads as (group, head in group), the chunk's positions minor.
        xc = x.reshape(bsz, nc, chunk, g, r, p).transpose(0, 1, 3, 4, 2, 5)
        dtc = dt.reshape(bsz, nc, chunk, g, r).transpose(0, 1, 3, 4, 2)
        bc = b.reshape(bsz, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
        cc = c.reshape(bsz, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
        # Log decay up to and including each position of its chunk.
        cum = jnp.cumsum(dtc * a.astype(jnp.float32).reshape(g, r, 1),
                         axis=-1)                        # [B,nc,G,R,Q]
        xdt = xc * dtc[..., None]                        # [B,nc,G,R,Q,P]
        # Inside a chunk: position q reads s <= q through C_q·B_s and the
        # decay between them.
        cb = jnp.einsum("bcgqn,bcgsn->bcgqs", cc, bc, precision=_HIGHEST)
        between = cum[..., :, None] - cum[..., None, :]  # [B,nc,G,R,Q,S]
        causal = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
        scores = cb[:, :, :, None] * jnp.exp(
            jnp.where(causal, between, -jnp.inf))
        y = jnp.einsum("bcgrqs,bcgrsp->bcgrqp", scores, xdt,
                       precision=_HIGHEST)
        # What each chunk adds to the state by its end, and how much of
        # the state entering it is left by then.
        to_end = jnp.exp(cum[..., -1:] - cum)            # [B,nc,G,R,Q]
        added = jnp.einsum("bcgrsp,bcgsn->bcgrpn", xdt * to_end[..., None],
                           bc, precision=_HIGHEST)       # [B,nc,G,R,P,N]
        kept = jnp.exp(cum[..., -1])                     # [B,nc,G,R]

        # The state each chunk enters with: a short recurrence over the
        # chunks, unrolled (8 of them in a 1024 bucket).  Not a
        # `lax.scan`: with seven such `while` loops in one prefill program
        # the v5e hung at 4 rows of 1024 (and at no other row count: PERF.md,
        # PR 31), and the loop buys nothing at this length.
        state = jnp.zeros((bsz, g, r, p, n), jnp.float32)
        entering, after = [], []
        for i in range(nc):
            if starts is not None and i:
                state = jnp.where(starts[:, i, None, None, None, None], 0.0,
                                  state)
            entering.append(state)
            state = kept[:, i, :, :, None, None] * state + added[:, i]
            after.append(state)
        entering = jnp.stack(entering, axis=1)           # [B,nc,G,R,P,N]
        y = y + jnp.einsum("bcgqn,bcgrpn->bcgrqp", cc, entering,
                           precision=_HIGHEST) * jnp.exp(cum)[..., None]
        y = y.transpose(0, 1, 4, 2, 3, 5).reshape(bsz, l + pad, h, p)[:, :l]
        y = y + d.astype(jnp.float32)[:, None] * x[:, :l]
        if packed is None:
            return y, state.reshape(bsz, h, p, n)
        # A prompt leaves the state after its last chunk, and padding
        # passes that on bit for bit (decay 1, input 0): so what the
        # prompt of chunk i leaves is what stands before the next start.
        # Selects over [B, ...] that fuse into the one write of the
        # result; the same rows taken by a gather at each prompt's end
        # compile, for the v5e, to a `while` over 2-MB slices (and a loop
        # in these programs is what hung the chip once: PERF.md, N6).
        left = [state] * nc
        for i in reversed(range(nc - 1)):
            left[i] = jnp.where(
                starts[:, i + 1, None, None, None, None], after[i],
                left[i + 1])
        return y, jnp.stack(left, axis=1).reshape(bsz, nc, h, p, n)


def ssd_step(state, x, dt, a, b, c, d):
    """One token a row.  state [B, H, P, N] float32; x [B, H, P];
    dt [B, H]; a, d [H]; b, c [B, G, N].  Returns (y [B, H, P] float32,
    the new state).  Elementwise in float32: the state is read and
    written once, which is all this costs."""
    h = x.shape[1]
    r = h // b.shape[1]
    with jax.named_scope("ssm.scan"):
        x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
        bh = jnp.repeat(b.astype(jnp.float32), r, axis=1)   # [B, H, N]
        ch = jnp.repeat(c.astype(jnp.float32), r, axis=1)
        decay = jnp.exp(dt * a.astype(jnp.float32))
        state = decay[:, :, None, None] * state \
            + (dt[:, :, None] * x)[..., None] * bh[:, :, None, :]
        y = jnp.sum(state * ch[:, :, None, :], axis=-1) \
            + d.astype(jnp.float32)[:, None] * x
        return y, state
