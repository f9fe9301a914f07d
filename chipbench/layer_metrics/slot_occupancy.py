"""slot_occupancy: share of decode slot-steps, inside the window, that held
a live request.  /metrics carries the engine's running ratio and its step
count; their product at the window's two edges gives the window's own."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    model = run["config"]["name"]
    edges = []
    for edge in ("open", "close"):
        text = run["scrapes"][edge]["metrics"]
        ratio = prom.sample(text, "kfserving_tpu_engine_slot_occupancy",
                            model=model)
        steps = prom.sample(text, "kfserving_tpu_engine_token_steps",
                            model=model)
        if ratio is None or steps is None:
            return None
        edges.append((ratio * steps, steps))
    steps = edges[1][1] - edges[0][1]
    return 100.0 * (edges[1][0] - edges[0][0]) / steps if steps > 0 else None
