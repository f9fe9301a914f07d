"""HBM accounting and eviction for multi-model serving.

The reference's multi-model story is disk-based: the agent puller downloads
artifacts and POSTs load/unload to the server (reference pkg/agent/
puller.go:120-183), and the shard strategy is a stub that always returns
shard 0 (reference pkg/controller/v1alpha1/trainedmodel/sharding/memory/
strategy.go:29-39) with a declared-memory field on the TrainedModel spec
(reference pkg/apis/serving/v1alpha1/trained_model.go:68-69).

On TPU "loaded" means *resident in HBM*, which is the scarce resource.  This
module makes the Memory field real (SURVEY.md §7 hard parts): an accountant
tracks declared/measured bytes per model against the device budget, and an
LRU policy picks eviction victims when a load would overflow.
"""

import logging
import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

from kfserving_tpu.observability import metrics as obs

logger = logging.getLogger("kfserving_tpu.hbm")


def device_hbm_stat(key: str, device=None) -> Optional[int]:
    """One entry of the device's `memory_stats()` (`bytes_limit`,
    `bytes_in_use`, `peak_bytes_in_use`); None where the backend
    reports no stats or not this one."""
    import jax

    device = device or jax.devices()[0]
    stats = getattr(device, "memory_stats", lambda: None)()
    if stats:
        return stats.get(key)
    return None


def device_hbm_bytes(device=None) -> Optional[int]:
    """Total HBM of the serving device, when the backend reports it."""
    return device_hbm_stat("bytes_limit", device)


def host_memory_bytes() -> int:
    """Available HOST memory (bytes), 0 when unknowable.  The host-
    side twin of `device_hbm_bytes`: this ledger budgets the device;
    the kv tier (engine/kv_tier.py) budgets its spill file against
    what the host can give without swapping the serving process —
    same admission discipline, one level down."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return 0


class InsufficientHBM(Exception):
    """No room for an admission.  `permanent` distinguishes "can
    NEVER fit" (bigger than the whole budget) from the transient
    no-evictable-victim case a waiting fault-in may retry."""

    permanent = False


@dataclass
class Residency:
    name: str
    bytes: int
    loaded_at: float
    last_used: float


class HBMManager:
    """Bin-packing accountant for model residency on one device/mesh.

    budget_bytes: capacity to pack into (defaults to `KFS_HBM_BUDGET`
    when set, else 90% of reported HBM, or a conservative 12 GiB if the
    backend doesn't report — v5e has 16 GiB).
    evict_cb: called with a model name when the manager decides to evict; the
    callback must actually free the model (engine.close() / offload()).
    victim_ok: optional admission-aware veto, consulted in LRU order
    while planning an eviction (called UNDER the ledger lock; the
    residency manager uses it to claim a victim atomically against a
    racing fault-in and to protect models with queued/in-flight work).
    A vetoed candidate is skipped — never evicted — and counted in
    `kfserving_tpu_hbm_eviction_skips_total`.
    victim_release: called for claimed-but-uncommitted victims when the
    admission plan fails after claiming them (undoes victim_ok's claim).
    """

    DEFAULT_BUDGET = 12 * 1024**3

    def __init__(self, budget_bytes: Optional[int] = None,
                 evict_cb: Optional[Callable[[str], None]] = None,
                 headroom: float = 0.10,
                 victim_ok: Optional[Callable[[str], bool]] = None):
        if budget_bytes is None:
            env = os.environ.get("KFS_HBM_BUDGET", "")
            if env:
                budget_bytes = int(float(env))
        if budget_bytes is None:
            total = device_hbm_bytes()
            budget_bytes = (int(total * (1 - headroom)) if total
                            else self.DEFAULT_BUDGET)
        self.budget_bytes = budget_bytes
        self.evict_cb = evict_cb
        self.victim_ok = victim_ok
        self.victim_release: Optional[Callable[[str], None]] = None
        self._resident: "OrderedDict[str, Residency]" = OrderedDict()
        self._lock = threading.Lock()
        # Lifetime eviction / admission-skip counts per model — the
        # ledger-side evidence the multimodel_density bench commits.
        self.evictions: Dict[str, int] = {}
        self.eviction_skips: Dict[str, int] = {}
        # Busy candidates already counted for a still-waiting
        # admission (admitted name -> candidates): a fault-in retries
        # admit every ~20 ms while its victims are busy, and the skip
        # metric counts each candidate once per admission episode,
        # not once per retry.
        self._skips_counted: Dict[str, set] = {}
        obs.hbm_budget_bytes().set(float(budget_bytes))

    @property
    def used_bytes(self) -> int:
        return sum(r.bytes for r in self._resident.values())

    @property
    def free_bytes(self) -> int:
        return self.budget_bytes - self.used_bytes

    def resident_models(self) -> List[str]:
        return list(self._resident.keys())

    def can_fit(self, nbytes: int) -> bool:
        return nbytes <= self.free_bytes

    def admit(self, name: str, nbytes: int, evict: bool = True) -> List[str]:
        """Account for a model of `nbytes` being loaded.

        Returns the list of models evicted to make room.  Raises
        InsufficientHBM if the model can never fit (bigger than budget) or
        eviction is disabled and there is no room.

        Three phases: RESERVE (plan victims and book `name`'s bytes
        under the lock — victims stay accounted), physical EVICTION
        (evict_cb outside the lock), COMMIT (victims leave the
        ledger).  Victims' bytes are not marked free until they are
        physically out of HBM: a concurrent admission on the other
        fault-in worker planning against freed-but-still-placed bytes
        would device_put straight into a transient overcommit/OOM.
        During the eviction window `used_bytes` therefore counts BOTH
        the victims and the incoming model — deliberately
        conservative.
        """
        victims: List[str] = []
        skipped: List[str] = []
        claimed: List[str] = []
        victim_entries: Dict[str, Residency] = {}
        try:
            with self._lock:
                if nbytes > self.budget_bytes:
                    err = InsufficientHBM(
                        f"model {name} needs {nbytes} bytes; budget is "
                        f"{self.budget_bytes}")
                    err.permanent = True
                    raise err
                # Plan admission against a scratch copy so a failed
                # admit leaves the books untouched (nothing is
                # physically evicted unless the plan fully reserves —
                # evict_cb never runs for a failed plan).  A reload of
                # `name` replaces its old entry rather than
                # double-counting it.
                plan = OrderedDict(
                    (k, v) for k, v in self._resident.items()
                    if k != name)
                while True:
                    plan_free = self.budget_bytes - sum(
                        r.bytes for r in plan.values())
                    if nbytes <= plan_free:
                        break
                    if not evict:
                        raise InsufficientHBM(
                            f"model {name} needs {nbytes} bytes; only "
                            f"{plan_free} free and eviction disabled")
                    # LRU order, admission-aware: victim_ok vetoes (and
                    # counts) candidates with queued/in-flight work; a
                    # passing candidate is CLAIMED under this lock, so
                    # a fault-in racing this eviction serializes on the
                    # ledger instead of serving a half-evicted model.
                    victim = None
                    for cand in plan:
                        if cand in skipped:
                            continue
                        if self.victim_ok is None or self.victim_ok(cand):
                            victim = cand
                            break
                        skipped.append(cand)
                    if victim is None:
                        raise InsufficientHBM(
                            f"model {name} needs {nbytes} bytes; no "
                            f"evictable victim ({len(skipped)} "
                            f"candidate(s) busy, nothing else to evict)")
                    plan.pop(victim)
                    victims.append(victim)
                    claimed.append(victim)
                # RESERVE: book the incoming bytes now; victims remain
                # in the ledger (claimed, so no other plan can take
                # them) until their physical offload lands below.
                now = time.time()
                self._resident.pop(name, None)
                self._resident[name] = Residency(name, nbytes, now, now)
                victim_entries = {v: self._resident[v] for v in victims}
                claimed = []  # reserved: this plan owns the victims now
        except BaseException:
            # Failed plan: undo victim_ok's claims so the candidates
            # rejoin the evictable set (books untouched by design).
            if self.victim_release is not None:
                for cand in claimed:
                    self.victim_release(cand)
            self._count_skips(name, skipped, done=False)
            raise
        self._count_skips(name, skipped, done=True)
        for victim in victims:
            logger.info("evicting model %s to fit %s", victim, name)
            if self.evict_cb:
                # Per-victim isolation: the plan is reserved, and the
                # callback's own cleanup demotes the record state —
                # one victim's failed physical offload must not strand
                # the REMAINING victims in their claimed ('evicting')
                # state with no offload ever coming, which would hang
                # every future fault-in of those models.
                try:
                    self.evict_cb(victim)
                except Exception:
                    logger.exception(
                        "evict callback failed for %s (entry released "
                        "anyway)", victim)
        if victims:
            # COMMIT: victims leave the ledger only now that they are
            # physically out of HBM.  Identity-checked pop: a victim
            # whose offload completed may have already been faulted
            # BACK in by a racing request (its record went host ->
            # faulting -> resident with a fresh ledger entry) — that
            # new residency must survive this commit.  Counter updates
            # stay under the lock (two fault-in workers race these
            # read-modify-writes).
            popped: List[str] = []
            with self._lock:
                for victim, entry in victim_entries.items():
                    if self._resident.get(victim) is entry:
                        self._resident.pop(victim)
                        popped.append(victim)
                    self.evictions[victim] = \
                        self.evictions.get(victim, 0) + 1
            for victim in victims:
                obs.hbm_evictions_total().labels(model=victim).inc()
            # Prune only victims that actually LEFT the ledger: one
            # re-admitted mid-eviction has a live entry (and a freshly
            # set gauge) this commit preserved.
            for victim in popped:
                obs.hbm_resident_bytes().prune(model=victim)
        obs.hbm_resident_bytes().labels(model=name).set(float(nbytes))
        return victims

    def _count_skips(self, name: str, skipped: List[str],
                     done: bool) -> None:
        """Count busy candidates an admission plan passed over — once
        per admission EPISODE, not per ~20 ms retry of a waiting
        fault-in.  `done` (plan committed) closes the episode."""
        with self._lock:
            counted = self._skips_counted.setdefault(name, set())
            fresh = [c for c in skipped if c not in counted]
            counted.update(fresh)
            if done:
                self._skips_counted.pop(name, None)
            for cand in fresh:
                self.eviction_skips[cand] = \
                    self.eviction_skips.get(cand, 0) + 1
        for cand in fresh:
            obs.hbm_eviction_skips_total().labels(
                model=cand, reason="busy").inc()

    def end_skip_episode(self, name: str) -> None:
        """Close a waiting admission's skip-dedup episode without a
        commit: the residency manager calls this when a fault-in
        exhausts its admit wait (or fails outright), so a LATER
        independent admission of the same model counts its busy
        victims afresh instead of being suppressed by the dead
        episode's memory."""
        with self._lock:
            self._skips_counted.pop(name, None)

    def touch(self, name: str) -> None:
        """Mark a model as recently used (moves it to MRU position)."""
        with self._lock:
            res = self._resident.get(name)
            if res is not None:
                res.last_used = time.time()
                self._resident.move_to_end(name)

    def release(self, name: str) -> None:
        with self._lock:
            self._resident.pop(name, None)
        # Prune, not zero: a released model must drop OUT of /metrics
        # (a forever-0 series per unloaded model would grow the scrape
        # unboundedly under multi-model churn).
        obs.hbm_resident_bytes().prune(model=name)

    def commit(self, staging: str, name: str,
               nbytes: Optional[int] = None) -> None:
        """Atomically replace ``name``'s entry with the ``staging`` entry.

        Used by zero-downtime reload: releasing old+staging and re-admitting
        would open a window where a concurrent admit claims the freed bytes
        and the re-admit fails after the new engine is already serving.
        Under the manager lock there is no such window.  ``nbytes``
        overrides the staged estimate with the measured size.
        """
        with self._lock:
            staged = self._resident.pop(staging, None)
            old = self._resident.pop(name, None)
            src = staged or old
            if src is None:
                return
            final = nbytes if nbytes is not None else src.bytes
            self._resident[name] = Residency(
                name, final, src.loaded_at, time.time())
        obs.hbm_resident_bytes().prune(model=staging)
        obs.hbm_resident_bytes().labels(model=name).set(float(final))

    def stats(self) -> Dict[str, float]:
        return {
            "budget_bytes": self.budget_bytes,
            "used_bytes": self.used_bytes,
            "free_bytes": self.free_bytes,
            "resident_models": len(self._resident),
            "evictions_total": sum(self.evictions.values()),
            "eviction_skips_total": sum(self.eviction_skips.values()),
        }

    def debug(self) -> Dict[str, Any]:
        """The `/debug/cache` HBM snapshot: budget totals plus the
        per-model residency ledger in LRU order (index 0 = next
        eviction victim) — what the multi-model residency manager
        (ROADMAP item 4) will consume."""
        with self._lock:
            residents = [
                {"model": r.name, "bytes": r.bytes,
                 "loaded_at": round(r.loaded_at, 3),
                 "last_used": round(r.last_used, 3)}
                for r in self._resident.values()]
        return {
            "budget_bytes": self.budget_bytes,
            "used_bytes": sum(r["bytes"] for r in residents),
            "resident": residents,
            "evictions": dict(self.evictions),
            "eviction_skips": dict(self.eviction_skips),
        }
