"""paged_blocks_per_iteration: KV blocks the paged decode kernel walked
between the window's edges over the loop iterations it needed for them.
Where the pool is narrow (grouped-query models: a block pair of K and V is a
quarter of a MiB or less) an iteration of `paged_attention_tpu` takes several
consecutive blocks of a row, because an iteration costs half a microsecond
whatever it copies; where the pool is wide it takes one, and this reads 1.0.
The engine counts both on the host where it accounts a delivered wave, with
the rule the kernel asks (`ops/paged_attention.blocks_per_iteration`): a
row's ceil(context / block_size) blocks and ceil(blocks / n) iterations a
step.  For a model with sliding-window layers one layer of each pool, summed
(a full ring of 9 is 4 + 4 + 1).  None for a program without the counter (a
parent commit)."""

from chipbench import prom

UNIT, LAYER, SOURCE = "blocks", "kernels", "program_counter"
MOVES = "tpot_p50_ms"

SERIES = "kfserving_tpu_generator_decode_kv{}_{}_total"


def read(run):
    model = run["config"]["name"]

    def moved(what: str, **pool):
        return prom.delta(run["scrapes"], "open", "close",
                          SERIES.format("_pool" if pool else "", what),
                          model=model, **pool)

    pools = [{"pool": "global"}, {"pool": "window"}]
    if moved("walk_iterations", **pools[0]) is None:
        pools = [{}]  # one pool: the series without the label
    blocks = iterations = 0
    for pool in pools:
        walked, looped = (moved(what, **pool)
                          for what in ("blocks_walked", "walk_iterations"))
        if walked is None or looped is None:
            return None
        blocks, iterations = blocks + walked, iterations + looped
    return blocks / iterations if iterations else None
