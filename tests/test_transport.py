"""Transport tests: pause/drain backpressure contract over the memory backend."""

import pytest

from apmbackend_tpu.transport import MemoryBroker, QueueManager, make_queue_manager


def make_qm(capacity=5):
    broker = MemoryBroker(capacity=capacity, low_water_ratio=0.4)
    qm = make_queue_manager({"brokerBackend": "memory", "statLogIntervalInSeconds": 60}, broker=broker)
    return qm, broker


def test_basic_produce_consume():
    qm, broker = make_qm()
    got = []
    prod = qm.get_queue("q1", "p")
    cons = qm.get_queue("q1c", "c", got.append)  # distinct names: one handle per queue
    # point consumer at q1 by registering directly on the same queue name:
    qm2 = make_queue_manager({"brokerBackend": "memory"}, broker=broker)
    cons = qm2.get_queue("q1", "c", got.append)
    cons.start_consume()
    prod.write_line("tx|a|b|c|1|2|3|4|N")
    broker.pump()
    assert got == ["tx|a|b|c|1|2|3|4|N"]


def test_backpressure_pause_and_drain_resume():
    broker = MemoryBroker(capacity=3, low_water_ratio=0.4)
    qm_prod = make_queue_manager({"brokerBackend": "memory"}, broker=broker)
    qm_cons = make_queue_manager({"brokerBackend": "memory"}, broker=broker)

    events = []
    qm_prod.on("pause", lambda: events.append("pause"))
    qm_prod.on("resume", lambda: events.append("resume"))

    prod = qm_prod.get_queue("q", "p")
    for i in range(5):
        prod.write_line(f"line{i}")

    # capacity 3 -> lines 3,4 buffered, pause emitted once
    assert events == ["pause"]
    assert prod.buffer_count() == 2
    assert broker.queue_depth("q") == 3

    got = []
    cons = qm_cons.get_queue("q", "c", got.append)
    cons.start_consume()
    broker.pump()  # drains queue; drain event fires -> retry buffers -> resume
    assert "resume" in events
    assert prod.buffer_count() == 0
    broker.pump()
    assert got == [f"line{i}" for i in range(5)]  # order preserved through buffer


def test_consumer_stop_start():
    qm, broker = make_qm()
    got = []
    prod = qm.get_queue("q", "p")
    qm2 = make_queue_manager({"brokerBackend": "memory"}, broker=broker)
    cons = qm2.get_queue("q", "c", got.append)
    cons.start_consume()
    prod.write_line("a")
    broker.pump()
    cons.stop_consume()
    prod.write_line("b")
    broker.pump()
    assert got == ["a"]
    assert broker.queue_depth("q") == 1  # message waits while cancelled
    cons.start_consume()
    broker.pump()
    assert got == ["a", "b"]


def test_get_queue_validation_and_reuse():
    qm, _ = make_qm()
    with pytest.raises(ValueError):
        qm.get_queue("x", "z")
    with pytest.raises(ValueError):
        qm.get_queue("x", "c")  # consumer without callback
    p1 = qm.get_queue("x", "p")
    p2 = qm.get_queue("x", "p")
    assert p1 is p2  # cached handle (queue.js:109-110)


def test_broker_introspection():
    qm, broker = make_qm()
    prod = qm.get_queue("q", "p")
    prod.write_line("hello")
    assert broker.queue_depth("q") == 1
    assert broker.queue_memory_bytes("q") == 5
    assert "q" in broker.queue_names()


def test_pump_thread_mode():
    import time

    broker = MemoryBroker(capacity=100)
    qm_p = make_queue_manager({"brokerBackend": "memory"}, broker=broker)
    qm_c = make_queue_manager({"brokerBackend": "memory"}, broker=broker)
    got = []
    prod = qm_p.get_queue("q", "p")
    cons = qm_c.get_queue("q", "c", got.append)
    cons.start_consume()
    broker.start_pump_thread()
    for i in range(50):
        prod.write_line(str(i))
    deadline = time.time() + 2.0
    while len(got) < 50 and time.time() < deadline:
        time.sleep(0.01)
    broker.stop()
    assert got == [str(i) for i in range(50)]


def test_pump_max_messages_exact():
    broker = MemoryBroker(capacity=100)
    qms = [make_queue_manager({"brokerBackend": "memory"}, broker=broker) for _ in range(4)]
    got = []
    for i in range(3):
        prod = qms[0].get_queue(f"q{i}", "p")
        cons = qms[i + 1].get_queue(f"q{i}", "c", got.append)
        cons.start_consume()
        prod.write_line(f"m{i}")
    assert broker.pump(max_messages=1) == 1
    assert len(got) == 1
    assert broker.pump() == 2


def test_drain_fires_per_refused_queue():
    """A queue that refused and then drained gets its drain event even while
    another refused queue stays full: in one process, a worker paused by its
    own full output queue stops consuming its input queue, so waiting for
    every queue to drain would deadlock."""
    broker = MemoryBroker(capacity=4)
    for name in ("stuck", "out"):
        broker.assert_queue(name)
    fired = []
    broker.on_drain(lambda: fired.append(True))
    for i in range(5):
        broker.send("stuck", b"x%d" % i)  # nobody consumes: stays full
        broker.send("out", b"y%d" % i)
    got = []
    broker.consume("out", got.append, "sink")
    broker.pump()
    assert len(got) == 4
    assert fired, "the drained queue's drain event waited on the stuck queue"
    assert broker.queue_depth("stuck") == 4
