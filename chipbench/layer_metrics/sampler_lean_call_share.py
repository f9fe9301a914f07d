"""sampler_lean_call_share: of the decode dispatches launched inside the
window, the share whose rows asked nothing of the sampler's tail but one
argmax over the [slots, vocabulary] logits: no row with a temperature (so
no support mask, no Gumbel draw, no second argmax) and no row that asked
for log-probabilities (so no top-N and no reductions).
kfserving_tpu_engine_sampler_tail_calls_total{program="decode"}, the
noise="0", logprobs="0" series over all of them, differenced between the
window's edges.  100 where every request is greedy and asks for no
log-probabilities, as the load generator's are.  None on a server without
the counter (a parent), or a window with no decode dispatch."""

from chipbench import histograms

UNIT, LAYER, SOURCE = "%", "model step", "program_counter"
MOVES = "tpot_p50_ms"
CALLS = "kfserving_tpu_engine_sampler_tail_calls_total"


def read(run):
    about = dict(model=run["config"]["name"], program="decode")
    calls = histograms.delta_summed(run["scrapes"], "open", "close", CALLS,
                                    **about)
    if not calls or calls <= 0:
        return None
    lean = histograms.delta_summed(run["scrapes"], "open", "close", CALLS,
                                   noise="0", logprobs="0", **about)
    return 100.0 * (lean or 0.0) / calls
