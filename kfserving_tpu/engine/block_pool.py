"""A pool's blocks, tabled by slot: the host side of a K/V block pool.

The device holds a pool's arrays (engine/programs.py) and reads a
dispatch's table (ops/paged_attention.py); who holds which block is kept
here.  A table is [slots, columns] of block ids, -1 for none, block `j`
of a slot's sequence in column `j % columns`: the whole-context pool has
a column for every block of a slot (`j % columns` is `j`, nothing is
ever recycled), a sliding-window pool a ring of them, which the sequence
goes round.

A block is free, held (counted once for every table it stands in), or
lingering: registered to the chain of prompt tokens it holds (`chain`)
and held by nobody, kept for reuse until an allocation finds the free
list empty and evicts the least recently released.  Only a pool whose
blocks are registered has lingering ones: a ring's allocator is its free
list alone.

Every method is a `_locked` helper: the caller holds
`GenerationEngine._block_lock`, and the pool takes no lock of its own.
"""

from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional

import numpy as np


class BlockPool:
    def __init__(self, name: str, blocks: int, slots: int, columns: int,
                 evicted: Optional[Callable[[int, Optional[bytes]],
                                            None]] = None):
        """`evicted(block, chain)` is told of a lingering block that an
        allocation takes, with the chain it held: that chain's fate (an
        index entry, a spill) is the caller's to decide."""
        self.name = name            # the `pool` label: "global", "window"
        self.blocks = blocks
        self.columns = columns
        self.table = np.full((slots, columns), -1, np.int32)
        # How many of its sequence's blocks each slot's row has taken in.
        self.covered = np.zeros(slots, np.int64)
        self.recycled = 0           # columns gone round again
        self.free: deque = deque(range(blocks))
        self.ref = np.zeros(blocks, np.int64)
        self.chain: Dict[int, bytes] = {}
        self.lingering: "OrderedDict[int, None]" = OrderedDict()
        self._evicted = evicted

    def alloc(self) -> Optional[int]:
        """A block nobody holds: a free one, else the least recently
        released of the lingering (prefix entries linger for reuse only
        until allocation pressure); None where there is neither."""
        if self.free:
            return self.free.popleft()
        if self.lingering:
            blk, _ = self.lingering.popitem(last=False)
            self._evicted(blk, self.chain.pop(blk, None))
            return blk
        return None

    def hold(self, blk: int) -> None:
        self.ref[blk] += 1
        self.lingering.pop(blk, None)

    def drop(self, blk: int) -> None:
        self.ref[blk] -= 1
        if self.ref[blk] <= 0:
            self.ref[blk] = 0
            if blk in self.chain:
                self.lingering[blk] = None  # linger for reuse
            else:
                self.free.append(blk)

    def place(self, slot: int, j: int, blk: int) -> None:
        """Hold `blk` as block `j` of the slot's sequence."""
        self.hold(blk)
        self.table[slot, j % self.columns] = blk
        self.covered[slot] = j + 1

    def at(self, slot: int, j: int) -> int:
        """The block tabled for block `j` of the slot's sequence."""
        return int(self.table[slot, j % self.columns])

    def take(self, slot: int, need: int) -> bool:
        """Take the sequence's blocks up to `need` into the slot's row:
        a column not held yet gets a block; one that is held is
        recycled, its block's oldest rows overwritten by the positions
        to come, with nothing to tell the device (the table does not
        change).  False where no block is to be had (freed ones wait
        out the deferral: the caller holds); what was taken stays, and
        `covered[slot]` says how far it got."""
        for j in range(int(self.covered[slot]), need):
            if self.table[slot, j % self.columns] >= 0:
                self.recycled += 1
                self.covered[slot] = j + 1
                continue
            blk = self.alloc()
            if blk is None:
                return False
            self.place(slot, j, blk)
        return True

    def release(self, slot: int) -> List[int]:
        """The slot's blocks, out of its row (all -1 from here).  They
        are still held: `give_back` frees them, once no dispatch in
        flight can write them."""
        blocks = [int(b) for b in self.table[slot] if b >= 0]
        self.table[slot, :] = -1
        self.covered[slot] = 0
        return blocks

    def give_back(self, blocks: List[int]) -> None:
        for blk in blocks:
            self.drop(blk)

    def snapshot(self) -> np.ndarray:
        """The table as a dispatch takes it: a copy, which a later
        release does not reach."""
        return self.table.copy()

    def tabled(self) -> int:
        """Table entries that hold a block (a shared block once for
        every row it stands in)."""
        return int(np.sum(self.table >= 0))
