"""programs_traced_in_window: programs JAX traced between the window's
edges, counted by JAX's own monitoring events in the served process
(kfserving_tpu_jax_compile_events_total{event="trace"}).  Must be 0, as
compiles_in_window must: it sees a retrace the log lines name too, and
needs no log."""

from chipbench import prom

UNIT, LAYER, SOURCE = "count", "caches", "program_counter"
MOVES = "setup_s"


def read(run):
    return prom.delta(run["scrapes"], "open", "close",
                      "kfserving_tpu_jax_compile_events_total",
                      event="trace")
