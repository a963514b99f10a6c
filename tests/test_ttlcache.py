"""TTLCache in deadline order against the full-scan cache it replaced.

The oracle below is the earlier algorithm, kept as the reference: a plain
dict whose sweep compares every entry's deadline with the clock. Seeded
random sequences of set (new and refreshed keys), get, delete, sweep,
flush_all and clear run on both, under a clock that never goes back and
one that sometimes steps back. Both must fire the same callbacks, in the
same order for every key that was not set again while in the cache (such
a key fires at its new place in deadline order), and return the same
get results, len and stats()."""

import random
import time
from typing import Any, Callable, Dict, Optional, Tuple

import pytest

from apmbackend_tpu.ingest.ttlcache import TTLCache


class FullScanTTLCache:
    """The full-scan TTL cache: deadlines kept in a plain dict in first-set
    order, every sweep compares all of them."""

    def __init__(self, ttl_s: float, *, on_expired: Optional[Callable[[str, Any], None]] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sweep_interval_s: Optional[float] = None):
        self.ttl_s = ttl_s
        self.on_expired = on_expired
        self.clock = clock
        self.sweep_interval_s = sweep_interval_s if sweep_interval_s is not None else max(ttl_s / 4, 1)
        self._store: Dict[str, Tuple[float, Any]] = {}
        self._last_sweep = clock()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._store)

    def set(self, key: str, value: Any) -> None:
        self._store[key] = (self.clock() + self.ttl_s, value)

    def get(self, key: str) -> Optional[Any]:
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

    def delete(self, key: str) -> None:
        self._store.pop(key, None)

    def sweep(self) -> int:
        now = self.clock()
        self._last_sweep = now
        expired = [(k, v) for k, (exp, v) in self._store.items() if now >= exp]
        for key, value in expired:
            del self._store[key]
            if self.on_expired:
                self.on_expired(key, value)
        return len(expired)

    def clear(self) -> int:
        n = len(self._store)
        self._store.clear()
        return n

    def flush_all(self) -> int:
        items = list(self._store.items())
        self._store.clear()
        for key, (_exp, value) in items:
            if self.on_expired:
                self.on_expired(key, value)
        return len(items)

    def stats(self) -> dict:
        return {"keys": len(self._store), "hits": self.hits, "misses": self.misses}


OPS = ("set", "set", "set", "get", "get", "delete", "sweep", "sweep", "advance", "advance",
       "flush_all", "clear")
KEYS = [f"k{i}" for i in range(16)]


@pytest.mark.parametrize("steps_back", [False, True], ids=["monotonic", "steps-back"])
@pytest.mark.parametrize("seed", range(20))
def test_ordered_cache_matches_full_scan(seed, steps_back):
    rng = random.Random(seed * 2 + steps_back)
    now = [100.0]
    clock = lambda: now[0]  # noqa: E731
    fired_new, fired_old = [], []
    new = TTLCache(10.0, clock=clock, on_expired=lambda k, v: fired_new.append((k, v)))
    old = FullScanTTLCache(10.0, clock=clock, on_expired=lambda k, v: fired_old.append((k, v)))
    reset = set()  # keys set again while in the cache, until they leave it
    value = 0
    n_fired = n_reset = 0
    for _ in range(600):
        op = rng.choice(OPS[:-2] if rng.random() < 0.97 else OPS[-2:])
        key = rng.choice(KEYS)
        mark_new, mark_old = len(fired_new), len(fired_old)
        deadlines = {k: exp for k, (exp, _v) in old._store.items()}
        examined0 = new.examined
        if op == "set":
            if key in old._store:
                reset.add(key)
                n_reset += 1
            value += 1
            new.set(key, value)
            old.set(key, value)
        elif op == "get":
            assert new.get(key) == old.get(key)
        elif op == "delete":
            new.delete(key)
            old.delete(key)
        elif op == "sweep":
            assert new.sweep() == old.sweep()
        elif op == "advance":
            if steps_back and rng.random() < 0.25:
                now[0] -= rng.uniform(0.0, 15.0)
            else:
                now[0] += rng.uniform(0.0, 8.0)
        elif op == "flush_all":
            assert new.flush_all() == old.flush_all()
        else:
            assert new.clear() == old.clear()
        got_new, got_old = fired_new[mark_new:], fired_old[mark_old:]
        assert sorted(got_new) == sorted(got_old)
        assert ([c for c in got_new if c[0] not in reset]
                == [c for c in got_old if c[0] not in reset])
        if not steps_back and op == "sweep":
            # deadline order, and the sweep compared the due entries and
            # one that is not due
            fired_deadlines = [deadlines[k] for k, _v in got_new]
            assert fired_deadlines == sorted(fired_deadlines)
            assert new.examined - examined0 == len(got_new) + (len(new) > 0)
        n_fired += len(got_new)
        reset &= set(old._store)
        assert len(new) == len(old)
        assert new.stats() == old.stats()
        assert list(new._store) == sorted(new._store, key=lambda k: new._store[k][0]) \
            or steps_back
    assert n_fired > 0 and n_reset > 0


def test_sweep_compares_only_due_entries():
    now = [0.0]
    fired = []
    c = TTLCache(30.0, clock=lambda: now[0], on_expired=lambda k, v: fired.append(k))
    for i in range(10_000):
        now[0] = i / 1000
        c.set(f"k{i}", i)
    now[0] = 30.0025  # k0, k1 and k2 are due
    examined0 = c.examined
    assert c.sweep() == 3
    assert fired == ["k0", "k1", "k2"]
    assert c.examined - examined0 == 4
    assert len(c) == 9_997


def test_step_back_scans_until_the_entries_are_in_order_again():
    now = [100.0]
    fired = []
    c = TTLCache(10.0, clock=lambda: now[0], on_expired=lambda k, v: fired.append(k))
    c.set("late", 1)  # deadline 110
    now[0] = 95.0
    c.set("early", 2)  # deadline 105: below the tail
    now[0] = 106.0
    examined0 = c.examined
    assert c.sweep() == 1 and fired == ["early"]
    assert c.examined - examined0 == 2  # a full scan
    now[0] = 107.0
    examined0 = c.examined
    assert c.sweep() == 0
    assert c.examined - examined0 == 1  # back in order: one peek
    now[0] = 111.0
    assert c.sweep() == 1 and fired == ["early", "late"]
