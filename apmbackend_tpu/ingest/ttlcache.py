"""TTL cache with expiry callbacks.

Role parity with the reference's NodeCache usage
(stream_parse_transactions.js:211-239): per-key TTL, periodic sweep, an
``expired`` callback that lets the parser salvage or discard incomplete
correlation state, and hit/miss statistics (logged every 60 s, :329-335).
The clock is injectable so log replay is deterministic.

Entries are kept in deadline order, so a sweep costs the entries that are
due, not the cache's size: the TTL is fixed per cache and the clock does
not go backwards, so deadline order is last-``set`` order. ``set`` moves a
refreshed key to the end, and ``sweep`` pops from the head while the head
is due. A clock that steps back (an injected test clock may) breaks the
order; the cache then scans every entry on each sweep, as an unordered
cache must, until a scan finds its entries in order again.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Callable, Optional, Tuple


class TTLCache:
    def __init__(
        self,
        ttl_s: float,
        *,
        on_expired: Optional[Callable[[str, Any], None]] = None,
        clock: Callable[[], float] = time.monotonic,
        sweep_interval_s: Optional[float] = None,
    ):
        self.ttl_s = ttl_s
        self.on_expired = on_expired
        self.clock = clock
        self.sweep_interval_s = sweep_interval_s if sweep_interval_s is not None else max(ttl_s / 4, 1)
        # key -> (expires_at, value), in last-set order
        self._store: "OrderedDict[str, Tuple[float, Any]]" = OrderedDict()
        self._ordered = True  # last-set order is deadline order
        self._tail = float("-inf")  # the newest deadline set while ordered
        self._last_sweep = clock()
        self.hits = 0
        self.misses = 0
        self.examined = 0  # deadlines the sweeps compared against the clock

    def __len__(self) -> int:
        return len(self._store)

    def set(self, key: str, value: Any) -> None:
        expires_at = self.clock() + self.ttl_s
        store = self._store
        store[key] = (expires_at, value)
        store.move_to_end(key)
        if expires_at >= self._tail:
            self._tail = expires_at
        else:
            self._ordered = False  # the clock stepped back

    def get(self, key: str) -> Optional[Any]:
        # one clock() read serves both the sweep check and the expiry test:
        # get() runs twice per emitted record on the frame fast path
        now = self.clock()
        if now - self._last_sweep >= self.sweep_interval_s:
            self.sweep()
        item = self._store.get(key)
        if item is None:
            self.misses += 1
            return None
        expires_at, value = item
        if now >= expires_at:
            del self._store[key]
            if self.on_expired:
                self.on_expired(key, value)
            self.misses += 1
            return None
        self.hits += 1
        return value

    def has(self, key: str) -> bool:
        return self.get(key) is not None

    def delete(self, key: str) -> None:
        self._store.pop(key, None)

    def maybe_sweep(self) -> None:
        now = self.clock()
        if now - self._last_sweep >= self.sweep_interval_s:
            self.sweep()

    def sweep(self) -> int:
        """Expire all overdue entries, firing callbacks in deadline order,
        which is the order of their last ``set``. That is first-``set``
        order, except for a key ``set`` again while in the cache: it fires
        in the place of its newest deadline. Returns count."""
        now = self.clock()
        self._last_sweep = now
        store = self._store
        expired = []
        if self._ordered:
            for key, (exp, value) in store.items():
                if now < exp:
                    self.examined += 1
                    break
                expired.append((key, value))
            self.examined += len(expired)
        else:
            # full scan; the order comes back once the survivors are sorted
            tail, ordered = float("-inf"), True
            for key, (exp, value) in store.items():
                if now >= exp:
                    expired.append((key, value))
                elif exp >= tail:
                    tail = exp
                else:
                    ordered = False
            self.examined += len(store)
            if ordered:
                self._ordered, self._tail = True, tail
        for key, value in expired:
            del store[key]
            if self.on_expired:
                self.on_expired(key, value)
        return len(expired)

    def clear(self) -> int:
        """Drop everything WITHOUT firing callbacks (end-of-replay partial
        discard; the native record cache exposes the same method)."""
        n = len(self._store)
        self._store.clear()
        return n

    def flush_all(self) -> int:
        """Expire everything regardless of TTL (end-of-replay drain)."""
        items = list(self._store.items())
        self._store.clear()
        for key, (_exp, value) in items:
            if self.on_expired:
                self.on_expired(key, value)
        return len(items)

    def stats(self) -> dict:
        return {"keys": len(self._store), "hits": self.hits, "misses": self.misses}
