"""engine/block_pool.py without an engine: a pool's blocks, tabled by
slot.  (The engine holds its `_block_lock` around every call; a test on
one thread needs none.)"""

import numpy as np
import pytest

from kfserving_tpu.engine.block_pool import BlockPool


def whole(blocks=6, slots=2, columns=4, evicted=None):
    return BlockPool("global", blocks, slots, columns, evicted=evicted)


def ring(blocks=3, slots=2, columns=3):
    return BlockPool("window", blocks, slots, columns)


def test_take_covers_a_slot_up_to_a_need_in_its_own_columns():
    pool = whole()
    assert pool.take(0, 3)
    assert pool.table[0].tolist() == [0, 1, 2, -1]
    assert pool.covered[0] == 3 and pool.tabled() == 3
    assert pool.take(0, 3)  # covered already: nothing taken
    assert list(pool.free) == [3, 4, 5]
    assert pool.ref[:3].tolist() == [1, 1, 1]
    assert [pool.at(0, j) for j in range(4)] == [0, 1, 2, -1]


def test_take_past_the_free_lists_end_keeps_what_it_took():
    pool = whole(blocks=3)
    assert pool.take(0, 2)
    assert not pool.take(1, 3)  # one block left for three
    assert pool.covered[1] == 1 and pool.table[1].tolist() == [2, -1, -1, -1]
    assert not pool.free and pool.alloc() is None
    pool.give_back(pool.release(0))
    assert pool.take(1, 3)  # goes on from where it stopped
    assert pool.table[1].tolist() == [2, 0, 1, -1]


@pytest.mark.parametrize("need,recycled", [(3, 0), (4, 1), (8, 5)])
def test_a_ring_recycles_column_j_mod_columns_and_counts_it(need, recycled):
    pool = ring()
    assert pool.take(0, need)
    assert sorted(pool.table[0].tolist()) == [0, 1, 2]  # never more
    assert pool.recycled == recycled and pool.covered[0] == need
    assert not pool.free
    # Block j stands in column j % columns, whichever round it is on.
    assert [pool.at(0, j) for j in range(need, need + 3)] == [
        int(pool.table[0, j % 3]) for j in range(need, need + 3)]


def test_a_whole_context_table_never_recycles():
    pool = whole(blocks=8, columns=4)
    assert pool.take(0, 4) and pool.take(1, 4)
    assert pool.recycled == 0
    assert sorted(pool.table.ravel().tolist()) == list(range(8))


def test_place_puts_a_prompts_last_blocks_into_a_ring():
    pool = ring(blocks=6)
    for j in range(7 - 3, 7):  # a prompt of 7 blocks, the last 3 alone
        pool.place(0, j, pool.alloc())
    assert pool.covered[0] == 7 and pool.recycled == 0
    assert [pool.at(0, j) for j in (4, 5, 6)] == [0, 1, 2]
    assert pool.table[0].tolist() == [2, 0, 1]  # 6 % 3, 4 % 3, 5 % 3
    assert pool.take(0, 8) and pool.recycled == 1  # block 7 over block 4


def test_release_returns_a_slots_blocks_and_clears_its_row():
    pool = whole()
    pool.take(0, 2), pool.take(1, 3)
    snap = pool.snapshot()
    assert pool.release(0) == [0, 1]
    assert pool.table[0].tolist() == [-1] * 4 and pool.covered[0] == 0
    assert pool.table[1].tolist() == [2, 3, 4, -1]  # the other row stays
    assert snap[0].tolist() == [0, 1, -1, -1]  # a snapshot is a copy
    # Released is not free: the blocks wait for `give_back`.
    assert list(pool.free) == [5] and pool.ref[:2].tolist() == [1, 1]
    assert pool.release(0) == []


def test_blocks_given_back_are_taken_again():
    pool = ring(blocks=3)
    assert pool.take(0, 3) and not pool.take(1, 1)
    pool.give_back(pool.release(0))
    assert list(pool.free) == [0, 1, 2] and not pool.ref.any()
    assert pool.take(1, 2)
    assert pool.table[1].tolist() == [0, 1, -1]


def test_a_shared_block_lingers_when_registered_and_frees_when_not():
    pool = whole(blocks=4)
    pool.take(0, 2)
    pool.chain[0] = b"chain-of-block-0"
    pool.place(1, 0, 0)  # a second slot points at the registered block
    assert pool.ref[0] == 2
    pool.give_back(pool.release(0))
    assert pool.ref[0] == 1 and not pool.lingering  # still held by slot 1
    assert list(pool.free) == [2, 3, 1]  # the unregistered one is free
    pool.give_back(pool.release(1))
    assert list(pool.lingering) == [0] and 0 not in pool.free
    pool.hold(0)  # a hit takes it out of the lingering
    assert not pool.lingering and pool.ref[0] == 1


def test_an_eviction_hands_back_the_chain_least_recently_released_first():
    evictions = []
    pool = whole(blocks=2, evicted=lambda blk, chain: evictions.append(
        (blk, chain)))
    pool.take(0, 2)
    pool.chain[0], pool.chain[1] = b"first", b"second"
    pool.release(0)
    pool.give_back([1, 0])  # block 1 released before block 0
    assert not pool.free and list(pool.lingering) == [1, 0]
    assert pool.alloc() == 1 and evictions == [(1, b"second")]
    assert pool.chain == {0: b"first"}  # the evicted registration is gone
    assert pool.alloc() == 0 and evictions[-1] == (0, b"first")
    assert pool.alloc() is None and len(evictions) == 2


def test_tables_are_int32_with_minus_one_for_none():
    pool = ring(slots=3)
    assert pool.table.dtype == np.int32 and pool.table.shape == (3, 3)
    assert (pool.snapshot() == -1).all() and pool.tabled() == 0
