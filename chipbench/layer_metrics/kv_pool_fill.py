"""kv_pool_fill: blocks of the paged KV pool in use, as a share of the pool,
averaged over the scrapes of the window (its edges and every slice)."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    scrapes = [run["scrapes"][e] for e in ("open", "close")
               if e in run["scrapes"]] + run["slice_scrapes"]
    values = [prom.sample(s["metrics"],
                          "kfserving_tpu_generator_pool_occupancy_ratio",
                          model=run["config"]["name"]) for s in scrapes]
    values = [v for v in values if v is not None]
    return 100.0 * sum(values) / len(values) if values else None
