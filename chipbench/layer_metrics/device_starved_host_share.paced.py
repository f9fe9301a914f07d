"""device_starved_host_share.paced: `device_starved_host_share` in the paced
cell, where it moves `request_mean_ms`: a request that arrives at an engine
at rest waits out the host's part of the hole before its prefill is on the
device.  Most of that cell's starved time is the loop waiting for a request
(cause="no_work"), which this leaves out and the run's earlier lines print.
None on a server without the counter (a parent)."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "program_counter"
MOVES = "request_mean_ms"


def read(run):
    scrapes = run["scrapes"]
    starved = prom.delta(
        scrapes, "open", "close",
        "kfserving_tpu_generator_device_starved_seconds_total",
        model=run["config"]["name"], cause="host")
    if starved is None:
        return None
    return 100.0 * starved / (scrapes["close"]["t"] - scrapes["open"]["t"])
