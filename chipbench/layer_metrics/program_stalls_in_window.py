"""program_stalls_in_window: launched programs that the engine counted as
stalled between the window's edges (their age in flight passed max(5 s,
20 x the running mean of their program) before their fetch returned):
kfserving_tpu_generator_program_stalls_total, summed over its programs,
differenced.  Must read 0; where it does not, the server's log holds one
`engine stalled:` line a stall with every row in flight.  None on a server
without the counter (a parent)."""

from chipbench import histograms

UNIT, LAYER, SOURCE = "count", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    return histograms.delta_summed(
        run["scrapes"], "open", "close",
        "kfserving_tpu_generator_program_stalls_total",
        model=run["config"]["name"])
