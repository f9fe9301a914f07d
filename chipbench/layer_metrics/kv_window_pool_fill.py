"""kv_window_pool_fill: blocks of the sliding-window layers' pool that a
slot's ring holds, as a share of that pool, averaged over the scrapes of the
window (its edges and every slice), as `kv_pool_fill` is for the
whole-context layers' pool.  A sequence holds at most its ring whatever its
length, so it cannot pass 100 and sits near the share of the pool's rings
that occupied slots fill.  It moves `tpot_p50_ms` here: a fuller ring pool is
more live rows a decode step, which is what a step's attention reads (the
cell is not judged on `tokens_per_s`, which `kv_pool_fill` moves: PERF.md
section 7, W1).  None for a program without the gauge."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    scrapes = [run["scrapes"][e] for e in ("open", "close")
               if e in run["scrapes"]] + run["slice_scrapes"]
    values = [prom.sample(s["metrics"],
                          "kfserving_tpu_generator_kv_pool_fill_ratio",
                          model=run["config"]["name"], pool="window")
              for s in scrapes]
    values = [v for v in values if v is not None]
    return 100.0 * sum(values) / len(values) if values else None
