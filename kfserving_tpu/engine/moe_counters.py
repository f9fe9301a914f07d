"""Routing counters of an expert model, from the device to /metrics.

The programs of a model with routed experts return, beside their tokens,
what the routers chose: per decode call (`programs.decode_call_stats`)
`pairs` [layers, experts] (token,
expert) pairs summed over the call's steps, `touched` (distinct experts
read, summed over the call's layer-steps) and `load_max` (the busiest
expert's pairs, summed likewise); per prefill dispatch `pairs` alone.  The
counts are over the rows a program computed: a parked decode row's garbage
step reads its experts too.  From a prefill dispatch's `pairs` and its
shape the host also counts what the grouped expert path was offered (the
padded bucket's tokens x choices a token, per expert layer) and how much
of it was real (each layer's real pairs in whole tiles of `ops/moe.py`'s:
the least its matmuls can visit, whichever way they went): no device
output and no fetch of its own.  A model that holds a share of its experts
(models/nemotron_h.py) counts all of these among the experts held, over its
expert layers, and reports beside them `elsewhere`, the pairs its routers
gave to experts that live on other chips.

The launching thread `note`s the handles; a fetch worker `drain`s those
that are ready after its own wave's fetch, so no launch and no fetch waits
for them.  A dense model's engine holds no `MoeCounters`.
"""

import threading
from collections import deque
from typing import Any, Dict

import numpy as np

from kfserving_tpu.observability import metrics as obs
from kfserving_tpu.ops import moe


class MoeCounters:
    def __init__(self, model: str, experts: int, per_token: int):
        self.model = model
        self.experts = experts
        self.per_token = per_token  # experts a token is routed to
        self.pairs = {"decode": 0, "prefill": 0}  # routed, by program
        self.elsewhere = 0     # routed to experts not held here
        self.touched = 0       # distinct experts read, over layer-steps
        self.layer_steps = 0   # decode layer-steps counted
        self.load_max = 0      # busiest expert's pairs, over layer-steps
        self.grouped_rows = 0           # offered the grouped path (prefill)
        self.grouped_rows_computed = 0  # of them real, in whole tiles
        self._pending: deque = deque()
        self._lock = threading.Lock()

    def note(self, program: str, handles: Dict[str, Any],
             layer_steps: int = 0, tokens: int = 0) -> None:
        """`layer_steps`: of a decode call; `tokens`: of a prefill
        dispatch, padding and all (rows x bucket)."""
        self._pending.append((program, handles, layer_steps,
                              moe.grouped_rows_offered(tokens,
                                                       self.per_token)))

    def drain(self) -> None:
        """Fetch and count every noted record whose arrays are ready
        (device order: all those launched before a fetched wave are)."""
        while True:
            try:
                record = self._pending.popleft()
            except IndexError:
                return
            program, handles, layer_steps, grouped_rows = record
            if not all(h.is_ready() for h in handles.values()):
                self._pending.appendleft(record)
                return
            # kfslint: disable=host-sync — ready arrays, on a fetch
            # worker inside the sanctioned fetch.
            host = {k: np.asarray(h) for k, h in handles.items()}
            pairs = int(host["pairs"].sum())
            with self._lock:
                self.pairs[program] += pairs
                obs.generator_moe_routed_pairs_total().labels(
                    model=self.model, program=program).inc(pairs)
                if "elsewhere" in host:
                    elsewhere = int(host["elsewhere"].sum())
                    self.elsewhere += elsewhere
                    obs.generator_moe_routed_pairs_elsewhere_total().labels(
                        model=self.model).inc(elsewhere)
                if grouped_rows:
                    offered = grouped_rows * host["pairs"].shape[0]
                    computed = int(moe.grouped_rows_real(
                        host["pairs"].sum(axis=-1)).sum())
                    self.grouped_rows += offered
                    self.grouped_rows_computed += computed
                    obs.generator_moe_grouped_pair_rows_total().labels(
                        model=self.model).inc(offered)
                    obs.generator_moe_grouped_pair_rows_computed_total(
                        ).labels(model=self.model).inc(computed)
                if layer_steps:
                    self.touched += int(host["touched"])
                    self.load_max += int(host["load_max"])
                    self.layer_steps += layer_steps
                    obs.generator_moe_experts_touched_total().labels(
                        model=self.model).inc(int(host["touched"]))
                    obs.generator_moe_expert_load_max_total().labels(
                        model=self.model).inc(int(host["load_max"]))
                    obs.generator_moe_layer_steps_total().labels(
                        model=self.model).inc(layer_steps)

    def stats(self) -> Dict[str, float]:
        with self._lock:
            grouped = {} if not self.grouped_rows else {
                "moe_grouped_rows_computed_share": round(
                    self.grouped_rows_computed / self.grouped_rows, 4)}
            if not self.layer_steps:
                return grouped
            mean_load = (self.pairs["decode"] / self.experts
                         / self.layer_steps)
            held = sum(self.pairs.values())
            return {
                **grouped,
                "moe_pairs_held_share": round(
                    held / max(1, held + self.elsewhere), 4),
                "moe_experts_touched_mean": round(
                    self.touched / self.layer_steps, 4),
                "moe_load_max_over_mean": round(
                    self.load_max / self.layer_steps / mean_load, 4),
            }
