"""Version-keyed result cache with delta-aware reuse (ISSUE 8).

The api's seed result cache keyed entries on the full segment-set
signature: correct, but every streamed append was a FULL invalidation —
a dashboard refreshing each second against a datasource appending each
second never hit.  This cache exploits the partial-aggregate-state
algebra instead (cf. arXiv:2603.26698: every aggregate state in the
engine is mergeable):

  * Entries key on the query identity + the DICTIONARY signature (never
    the segment uids) and carry the monotonic per-datasource `version`
    (catalog/cache.py — the hook PR 6 installed) plus the exact segment
    uid set the cached answer covered.
  * A version-exact lookup is a plain hit: the final frame serves with
    ZERO device dispatch.
  * A version-bumped lookup whose entry still covers a SUBSET of the
    live segment set (an append published new segments, none retired)
    reuses delta-aware: the engine scans ONLY the fresh segments,
    merges `(cached historical partial) ⊕ (fresh delta partials)`, and
    the refreshed entry re-caches at the new version — the append cost
    the delta, not the history.
  * A retired uid (compaction), a dictionary extension (the key
    changes), or a missing partial state (the answer came off the
    sparse/adaptive/mesh/fallback paths, which hold no dense state) is
    a full miss.

Writes go through `put(...)` with a REQUIRED keyword `version` — the
serving-discipline lint pass (GL1701) rejects result-cache writes that
do not carry the datasource version, because an unversioned entry is
exactly the stale-dashboard bug this cache exists to prevent.
"""

from __future__ import annotations

import threading
from typing import Dict, FrozenSet, Optional

from ..exec.state import nbytes
from ..utils.log import get_logger
from ..utils.lru import CountBudgetCache

log = get_logger("serve.result_cache")

# partial states larger than this are not retained (a cached [G, M]
# state is HOST RAM held per entry; a huge-G state would let 64 cached
# dashboards pin gigabytes) — the entry degrades to frame-only
_STATE_BYTES_MAX = 32 << 20


class CacheEntry:
    __slots__ = ("df", "state", "version", "uids", "hits", "delta_hits")

    def __init__(self, df, state, version: int, uids: FrozenSet):
        self.df = df
        self.state = state
        self.version = int(version)
        self.uids = frozenset(uids)
        self.hits = 0
        self.delta_hits = 0


class ResultCache:
    """LRU result cache of final frames + mergeable partial states."""

    def __init__(self, entries: int = 64, delta_reuse: bool = True):
        self.entries = max(int(entries), 0)
        self.delta_reuse = bool(delta_reuse)
        self._cache = CountBudgetCache(max(self.entries, 1))
        self._lock = threading.Lock()
        self.hits = 0
        self.delta_hits = 0
        self.misses = 0

    @property
    def enabled(self) -> bool:
        # capacity is a CACHE property; whether lookups happen at all is
        # the session config's live decision (the api layer gates on
        # `config.result_cache_entries > 0` per query, so flipping the
        # config mid-session enables/disables without a rebuild)
        return self._cache.budget_entries > 0

    def _count(self, outcome: str) -> None:
        from ..obs import get_registry

        with self._lock:
            if outcome == "hit":
                self.hits += 1
            elif outcome == "delta":
                self.delta_hits += 1
            else:
                self.misses += 1
        get_registry().counter(
            "sdol_result_cache_total",
            "result-cache lookups by outcome (hit = zero device "
            "dispatch; delta = cached historical ⊕ fresh delta)",
            labels=("outcome",),
        ).labels(outcome=outcome).inc()

    def get(self, key, version: int):
        """Version-exact hit: the cached final frame, or None.  Counts
        only genuine hits — the miss (and the delta outcome) is counted
        by the caller once it knows which path served."""
        if not self.enabled:
            return None
        entry: Optional[CacheEntry] = self._cache.get(key)
        if entry is None or entry.version != int(version):
            return None
        entry.hits += 1
        self._count("hit")
        return entry.df.copy()

    def reusable_entry(self, key, version: int, current_uids) -> Optional[
        CacheEntry
    ]:
        """The entry a delta-aware refresh can extend: present, stale by
        version, holding a partial state, and covering a strict SUBSET
        of the live segment uids (segments were appended, none retired).
        None otherwise."""
        if not self.enabled:
            return None
        entry: Optional[CacheEntry] = self._cache.get(key)
        if entry is None or entry.state is None:
            return None
        if entry.version == int(version):
            return None  # exact hit path should have served already
        current_uids = frozenset(current_uids)
        if not entry.uids < current_uids:
            return None  # retired/replaced segments: full miss
        return entry

    def note_delta_hit(self, entry: CacheEntry) -> None:
        entry.delta_hits += 1
        self._count("delta")

    def note_miss(self) -> None:
        if self.enabled:
            self._count("miss")

    def put(self, key, df, *, version: int, uids, state=None) -> None:
        """Publish one cached answer.  `version` (keyword-REQUIRED: the
        serving-discipline contract, GL1701) is the datasource snapshot
        version the answer was computed against; `uids` the snapshot's
        full segment uid set; `state` the merged host partial state when
        the execution path produced one (enables delta-aware reuse)."""
        if not self.enabled:
            return
        if state is not None and nbytes(state) > _STATE_BYTES_MAX:
            log.info(
                "partial state too large to retain (%d B); caching the "
                "frame only", nbytes(state),
            )
            state = None
        self._cache[key] = CacheEntry(
            df.copy(), state, version=version, uids=uids
        )

    def resize(self, entries: int) -> None:
        """`SET result_cache_entries` hook: re-budget and evict down (a
        0 budget releases every entry and disables the cache)."""
        self.entries = max(int(entries), 0)
        self._cache.resize(self.entries)

    def clear(self) -> None:
        self._cache.clear()

    def __len__(self) -> int:
        return len(self._cache)

    def to_dict(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._cache),
                "capacity": self.entries,
                "delta_reuse": self.delta_reuse,
                "hits": self.hits,
                "delta_hits": self.delta_hits,
                "misses": self.misses,
            }
