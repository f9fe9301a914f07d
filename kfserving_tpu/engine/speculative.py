"""Speculative decoding support: draft proposers + the draft-model
residency handle (ROADMAP item 2, ISSUE 20).

Decode on a real model is memory-bandwidth-bound: every wave re-reads
the full parameter set to emit ONE token per slot.  Speculative
decoding amortizes that read across K+1 tokens — a cheap *proposer*
guesses K tokens per live slot, the target model scores all K+1
positions in ONE Lq>1 dispatch (the chunk-prefill cache mode +
multi-position `logit_positions`, engine/generator.py), and the engine
accepts the longest prefix on which the target's own sampled token
agrees with the proposal.

Two proposers, one contract (`propose` K tokens per slot):

- **NGramProposer** — zero-cost prompt-lookup head (host-side): find
  the longest n-gram suffix of the slot's history earlier in the
  prompt+generated stream and replay the tokens that followed it.
  Free to run, surprisingly effective on the repetitive tails real
  generation produces, and the always-available fallback when no
  draft model is configured.
- **draft model** — a small registered decoder proposing greedily via
  a jitted rolling-window scan (`make_draft_proposer`).  The window
  rides RELATIVE positions 0..W-1: draft proposals are guesses, not
  truth — the verify dispatch is the oracle, so the draft never needs
  absolute-position fidelity (and one compile serves every wave).

Parity note (why exact-match acceptance is exact for sampling too):
the engine's sampler is deterministic given (seed, absolute position)
— noise is `fold_in(fold_in(base_key, seed), pos)` (generator.py).
The target's "sample" at position p is therefore a pure function of
the prefix, and classic rejection sampling against a point-mass draft
distribution degenerates to: accept iff the proposal EQUALS the
target's draw at p, else emit the target's draw.  That is bit-exact
with non-speculative decode for greedy AND seeded sampling — a
stronger guarantee than the distributional parity general rejection
sampling gives.

`DraftModel` is the residency-manager handle (engine/residency.py
managed-model contract): the draft registers beside the target as a
second model so the HBM ledger accounts both and `kfs models` shows
it; it is PINNED (offloadable=False) while the target engine serves —
evicting the draft mid-stream would silently flip live streams onto
the slower non-speculative path.
"""

import logging
from typing import Any, List, Optional, Sequence

import numpy as np

logger = logging.getLogger("kfserving_tpu.speculative")

# Longest n-gram the prompt-lookup head tries to match, descending to
# 1; 3 is the LLMA/prompt-lookup sweet spot — longer keys rarely
# re-occur, shorter ones mispredict.
NGRAM_MAX_N = 3
# Rolling draft window default: long enough for local coherence, small
# enough that K cache-less forwards stay a fraction of one target wave.
DEFAULT_DRAFT_WINDOW = 32


class NGramProposer:
    """Prompt-lookup proposer: propose the K tokens that followed the
    most recent earlier occurrence of the history's longest suffix
    n-gram.  Pure host-side numpy — zero device cost, zero extra HBM.
    """

    def __init__(self, k: int, max_n: int = NGRAM_MAX_N):
        self.k = int(k)
        self.max_n = int(max_n)

    def propose(self, history: Sequence[int]) -> List[int]:
        """K proposed continuation tokens for one slot.  A history
        with no repeated suffix proposes repeats of the last token —
        still a valid guess (verify rejects bad ones at zero parity
        cost; repetition is common enough that it pays for itself)."""
        hist = list(history)
        k = self.k
        n_hist = len(hist)
        fill = hist[-1] if hist else 0
        for n in range(min(self.max_n, n_hist - 1), 0, -1):
            key = hist[-n:]
            # Scan backwards for the most recent earlier occurrence —
            # recency matters: generation loops locally.
            for start in range(n_hist - n - 1, -1, -1):
                if hist[start:start + n] == key:
                    cont = hist[start + n:start + n + k]
                    if cont:
                        return (cont + [fill] * k)[:k]
        return [fill] * k


def rolling_windows(histories: Sequence[Sequence[int]], slots: int,
                    rows: Sequence[int], window: int) -> np.ndarray:
    """[slots, window] int32 draft-model input: each listed row's last
    `window` history tokens, left-padded with 0.  Unlisted rows stay
    zero — their proposals are garbage the verify dispatch parks."""
    ids = np.zeros((slots, window), np.int32)
    for row, hist in zip(rows, histories):
        tail = list(hist)[-window:]
        if tail:
            ids[row, window - len(tail):] = tail
    return ids


def make_draft_proposer(jax_mod, module, slots: int, window: int,
                        k: int):
    """Jitted greedy rolling-window proposer: (variables, ids[S, W])
    -> proposals [S, K].  Each scan step runs one cache-less full
    forward over the window, argmaxes the last position, and
    roll-appends — static shapes, one compile per (S, W, K).

    Greedy regardless of the request's sampling params: proposals are
    guesses, and exact-match acceptance guarantees parity whatever the
    proposer emits — greedy just maximizes the acceptance rate a tiny
    deterministic draft can reach."""
    jnp = jax_mod.numpy
    last_idx = jnp.full((slots,), window - 1, jnp.int32)

    def propose(variables, ids):
        def step(ids, _):
            logits = module.apply(variables, ids,
                                  logit_positions=last_idx)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            ids = jnp.concatenate([ids[:, 1:], nxt[:, None]], axis=1)
            return ids, nxt

        _, toks = jax_mod.lax.scan(step, ids, None, length=k)
        return jnp.swapaxes(toks, 0, 1)  # [S, K]

    return jax_mod.jit(propose)


class DraftModel:
    """Residency-manager handle for the draft (engine/residency.py
    managed-model contract).  The draft is a dependent of a live
    target engine, not an independently schedulable model: it
    registers as resident (ready + engine set), reports its param
    bytes for the HBM ledger, and vetoes eviction (offloadable=False)
    for as long as the target serves — the ResidencyManager's
    admission-aware eviction then never picks it as a victim."""

    def __init__(self, name: str, module: Any, variables: Any,
                 target_engine: Any, window: int = DEFAULT_DRAFT_WINDOW):
        self.name = name
        self.module = module
        self.variables = variables
        self.window = int(window)
        # Managed-model contract surface: a non-None engine + ready
        # registers the record directly in the "resident" state.
        self.engine = target_engine
        self.ready = True

    def param_bytes(self) -> int:
        import jax

        return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(self.variables))

    # -- residency hooks ---------------------------------------------------
    @property
    def offloadable(self) -> bool:
        """Pinned while the target engine is live: evicting the draft
        would silently degrade every in-flight stream to
        non-speculative decode."""
        return self.engine is None

    def offload(self) -> None:
        raise RuntimeError(
            f"draft model {self.name} is pinned while its target "
            "engine serves")

    def fault_in(self) -> None:
        """Nothing to restore: `variables` is the tree the target
        engine placed on its device when it was built (admitted with
        the target's load)."""

    def host_bytes(self) -> int:
        return self.param_bytes()

    def load(self) -> None:
        """Cold build is the target's job (the draft is materialized
        inside GenerativeModel.load); a standalone load is a no-op."""

    def release(self) -> None:
        """Unpin on target unload: the handle stops claiming an
        engine, so a lingering registration becomes evictable and
        `deregister` leaves no dangling veto.  It also lets go of the
        engine's device tree, which a lingering handle would otherwise
        keep in HBM."""
        self.engine = None
        self.variables = None
