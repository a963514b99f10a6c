"""In-memory broker backend: the fake broker the reference never had.

Bounded per-queue deques with the pause/drain contract: ``send`` returns False
when a queue crosses its high-water mark; once the depth falls to the
low-water mark a drain callback fires on the producer channel. Delivery is
either *pumped* deterministically (tests, single-process pipelines) or driven
by a background thread (live mode).

The broker object is shareable between modules in one process, standing in for
the external RabbitMQ server; queue depth/memory introspection mirrors what
``rabbitmqctl list_queues`` provided the manager (apm_manager.js:429-453).

At-least-once (``manual_ack``) consumers get RabbitMQ's unacked-ledger
semantics: a delivered message moves to the broker's unacked map instead of
vanishing, ``ack(tokens)`` discards it, and anything still unacked when the
consumer channel closes — or when :meth:`MemoryBroker.bounce` simulates a
broker restart — is requeued at the FRONT of its queue with
``headers["redelivered"]`` set, exactly what a real broker does after a
consumer dies mid-flight.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from .base import Channel, accepts_headers


class _NamedQueue:
    def __init__(self, capacity: int):
        self.capacity = capacity
        # (payload, headers) pairs — headers carry the transport-entry
        # ingest_ts stamp through the fake broker like AMQP properties would
        self.items: deque = deque()
        # (tag, callback, wants_headers, manual_ack)
        self.consumers: List[Tuple[str, Callable, bool, bool]] = []


class MemoryBroker:
    """Process-local named-queue store shared by producer/consumer channels."""

    def __init__(self, capacity: int = 10000, low_water_ratio: float = 0.5):
        self.capacity = capacity
        self.low_water_ratio = low_water_ratio
        self._queues: Dict[str, _NamedQueue] = {}  # guarded-by: _lock
        self._lock = threading.RLock()
        self._drain_callbacks: List[Callable[[], None]] = []  # guarded-by: _lock
        # queues that refused a send since the last drain event
        self._full: set = set()  # guarded-by: _lock
        self._pump_thread: Optional[threading.Thread] = None
        # one deliverer at a time, whether the pump thread or a caller's
        # pump(): a consumer callback may push into a single-producer
        # structure (the worker's intake ring), and a caller whose pump()
        # finds nothing knows no delivery is still running elsewhere
        # (StandalonePipeline._settle)
        self._deliver_lock = threading.RLock()
        self._stop = threading.Event()
        self._work = threading.Event()
        # manual-ack ledger: token -> (queue_name, payload, headers), in
        # delivery order (dict preserves insertion order — requeue walks it
        # newest-last so redelivery keeps the original FIFO)
        self._unacked: Dict[int, Tuple[str, bytes, Optional[dict]]] = {}  # guarded-by: _lock
        self._next_token = 0  # guarded-by: _lock

    # -- queue admin ---------------------------------------------------------
    def assert_queue(self, name: str) -> None:
        with self._lock:
            if name not in self._queues:
                self._queues[name] = _NamedQueue(self.capacity)

    def queue_depth(self, name: str) -> int:
        with self._lock:
            q = self._queues.get(name)
            return len(q.items) if q else 0

    def queue_names(self) -> List[str]:
        with self._lock:
            return list(self._queues)

    def queue_memory_bytes(self, name: str) -> int:
        with self._lock:
            q = self._queues.get(name)
            return sum(len(p) for p, _h in q.items) if q else 0

    # -- producer side -------------------------------------------------------
    def send(self, name: str, payload: bytes, headers: Optional[dict] = None) -> bool:
        with self._lock:
            q = self._queues[name]
            if len(q.items) >= q.capacity:
                self._full.add(name)
                return False
            q.items.append((payload, headers))
        self._work.set()
        return True

    def on_drain(self, callback: Callable[[], None]) -> None:
        with self._lock:
            self._drain_callbacks.append(callback)

    # -- consumer side -------------------------------------------------------
    def consume(self, name: str, callback: Callable[[bytes], None], tag: str,
                manual_ack: bool = False) -> None:
        with self._lock:
            q = self._queues[name]
            if not any(t == tag for t, _cb, _h, _m in q.consumers):
                q.consumers.append((tag, callback, accepts_headers(callback), manual_ack))
        self._work.set()

    def cancel(self, tag: str) -> None:
        # cancel does NOT requeue unacked deliveries: pause/resume cycles
        # cancel and the in-flight epoch must keep its tokens ackable
        with self._lock:
            for q in self._queues.values():
                q.consumers = [c for c in q.consumers if c[0] != tag]

    def ack(self, tokens) -> None:
        """Discard manual-ack deliveries (idempotent; stale tokens ignored)."""
        with self._lock:
            for t in tokens:
                self._unacked.pop(t, None)

    def unacked_count(self, name: Optional[str] = None) -> int:
        with self._lock:
            if name is None:
                return len(self._unacked)
            return sum(1 for q, _p, _h in self._unacked.values() if q == name)

    def requeue_unacked(self) -> int:
        """Requeue every unacked delivery at the FRONT of its queue (original
        order preserved), marking ``headers["redelivered"]`` — what RabbitMQ
        does when a consumer connection dies. Returns the requeue count."""
        with self._lock:
            pending = list(self._unacked.items())
            self._unacked.clear()
            for _tok, (name, payload, headers) in reversed(pending):
                headers = dict(headers or {})
                headers["redelivered"] = True
                self._queues[name].items.appendleft((payload, headers))
        if pending:
            self._work.set()
        return len(pending)

    def bounce(self) -> int:
        """Simulate a broker restart for chaos tests: redeliver everything
        unacked. (Acked messages were already removed — durability holds.)"""
        return self.requeue_unacked()

    # -- delivery ------------------------------------------------------------
    def pump(self, max_messages: Optional[int] = None) -> int:
        """Deliver pending messages to registered consumers; returns count.

        Ack-on-receipt consumers get the message removed before the callback
        runs; manual-ack consumers get it moved to the unacked ledger and a
        token as third callback arg.
        """
        with self._deliver_lock:
            return self._pump(max_messages)

    def _pump(self, max_messages: Optional[int]) -> int:
        delivered = 0
        while max_messages is None or delivered < max_messages:
            with self._lock:
                batch = []
                budget = None if max_messages is None else max_messages - delivered
                for qname, q in self._queues.items():
                    if budget is not None and len(batch) >= budget:
                        break
                    if q.consumers and q.items:
                        payload, headers = q.items.popleft()
                        _tag, cb, wants_headers, manual = q.consumers[0]
                        token = None
                        if manual:
                            self._next_token += 1
                            token = self._next_token
                            self._unacked[token] = (qname, payload, headers)
                        batch.append((cb, payload, headers, wants_headers, manual, token))
                if not batch:
                    break
            for cb, payload, headers, wants_headers, manual, token in batch:
                if manual:
                    cb(payload, headers, token)
                elif wants_headers:
                    cb(payload, headers)
                else:
                    cb(payload)
                delivered += 1
            self._maybe_drain()
        self._maybe_drain()
        return delivered

    def _maybe_drain(self) -> None:
        with self._lock:
            # drain = a queue that refused can take sends again. Waiting for
            # EVERY queue deadlocks one process: a worker paused by its own
            # refused db_insert producer stops consuming transactions, which
            # then never drains (seen replaying 8k keys standalone). A retry
            # into a queue that is still full just refuses again.
            drained = {n for n in self._full
                       if len(self._queues[n].items) <= self._queues[n].capacity * self.low_water_ratio}
            if not drained:
                return
            self._full -= drained
            callbacks = list(self._drain_callbacks)
        for cb in callbacks:
            cb()

    def start_pump_thread(self) -> None:
        if self._pump_thread is not None:
            return

        def _loop():
            while not self._stop.is_set():
                if self.pump() == 0:
                    self._work.clear()
                    self._work.wait(timeout=0.05)

        self._pump_thread = threading.Thread(target=_loop, name="memory-broker-pump", daemon=True)
        self._pump_thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._work.set()
        if self._pump_thread is not None:
            self._pump_thread.join(timeout=2.0)
            self._pump_thread = None


class MemoryChannel(Channel):
    """Channel view over a shared MemoryBroker."""

    def __init__(self, broker: MemoryBroker):
        self.broker = broker

    def assert_queue(self, name: str) -> None:
        self.broker.assert_queue(name)

    def send(self, name: str, payload: bytes, headers: Optional[dict] = None) -> bool:
        return self.broker.send(name, payload, headers)

    def consume(self, name: str, callback, consumer_tag: str, manual_ack: bool = False) -> None:
        self.broker.consume(name, callback, consumer_tag, manual_ack=manual_ack)

    def ack(self, tokens) -> None:
        self.broker.ack(tokens)

    def cancel(self, consumer_tag: str) -> None:
        self.broker.cancel(consumer_tag)

    def on_drain(self, callback) -> None:
        self.broker.on_drain(callback)

    def queue_lag(self, name: str) -> int:
        """Waiting depth plus unacked in-flight deliveries — the backlog the
        consumer still owes. Scrape-time view for the ``apm_queue_lag``
        gauge (the per-queue lag SLO input), uniform with the spool's."""
        return self.broker.queue_depth(name) + self.broker.unacked_count(name)

    def close(self) -> None:
        # redelivery-on-close: a closing consumer channel abandons its
        # unacked deliveries back to the queues (RabbitMQ connection-death
        # semantics) so the next consumer — or the restarted process on a
        # shared broker — sees them again
        self.broker.requeue_unacked()
