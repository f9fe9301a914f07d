"""device_starved_host_share: of the window, the share in which the device
had nothing from the engine and the host was why: the scheduler loop
admitting, growing or delivering, the launching thread preparing or inside a
launch call with nothing behind it on the device.
Δkfserving_tpu_generator_device_starved_seconds_total{cause="host"} between
the window's edges over the seconds between the two scrapes.  The time the
loop stood waiting for a request (cause="no_work") is not in it.  A lower
bound of the device's idle time, measured over the whole window in every
traced run, where `device_idle_share.generate` reads the capture's last 3 s,
edges included.  None on a server without the counter (a parent)."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    scrapes = run["scrapes"]
    starved = prom.delta(
        scrapes, "open", "close",
        "kfserving_tpu_generator_device_starved_seconds_total",
        model=run["config"]["name"], cause="host")
    if starved is None:
        return None
    return 100.0 * starved / (scrapes["close"]["t"] - scrapes["open"]["t"])
