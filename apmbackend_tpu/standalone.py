"""Single-process pipeline: every module wired over one in-process broker.

The reference can only run as 6 processes + RabbitMQ; this mode runs the whole
system — parser (tail or replay), TPU worker, DB sink, JMX poller — inside one
process over the memory broker. It is the dev/bench/test topology; production
parity mode is the supervisor + AMQP multi-process layout.
"""

from __future__ import annotations

import time
from typing import Optional

from .ingest import parser_main
from .ingest import jmx_main
from .runtime.module_base import ModuleRuntime
from .runtime.worker import WorkerApp
from .sinks import insert_db_main
from .transport.memory import MemoryBroker


class StandalonePipeline:
    def __init__(self, config_path: Optional[str] = None, config: Optional[dict] = None,
                 *, tail: bool = True, install_signals: bool = True):
        self.broker = MemoryBroker()
        self.broker.start_pump_thread()
        # the lead runtime owns signals + the config watcher; the rest share
        # its config object and broker
        self.lead = ModuleRuntime(
            "tpuEngine", config_path=config_path, config=config,
            broker=self.broker, install_signals=install_signals,
        )
        self.worker = WorkerApp(self.lead)
        self.sink_rt = ModuleRuntime("streamInsertDb", config=self.lead.config,
                                     broker=self.broker, install_signals=False)
        self.writer = insert_db_main.build(self.sink_rt)
        self.parser_rt = ModuleRuntime("streamParseTransactions", config=self.lead.config,
                                       broker=self.broker, install_signals=False)
        self.parser, self.tail_manager = parser_main.build(self.parser_rt, tail=tail)
        self.jmx_rt = ModuleRuntime("pullJvmStats", config=self.lead.config,
                                    broker=self.broker, install_signals=False)
        self.jmx = jmx_main.build(self.jmx_rt)
        self._closed = False
        # propagate hot reloads from the lead watcher to the satellites
        self.lead.on_reload(self._propagate_reload)
        # a signal on the lead must also run the satellites' exit handlers
        # (sink flush+resume, parser drain, tail stop) — registered after the
        # WorkerApp handler so LIFO order runs satellites first
        self.lead.on_exit(self.shutdown)

    def _propagate_reload(self, new_config: dict) -> None:
        for rt in (self.sink_rt, self.parser_rt, self.jmx_rt):
            rt._on_config_change(new_config)

    def replay(self, log_dir: str) -> int:
        """Feed every log file under ``log_dir`` through the parser, then drain.

        A replay reads far faster than the worker ticks, so after each chunk
        it waits until the pipeline is idle (``_settle``). A burst is then at
        most the ticks that one chunk's records open, and each tick emits a
        FullStat row per key and lag into the worker's ``db_insert``
        producer, whose pause buffer evicts its oldest lines past its cap
        (100,000). A 64 KiB chunk of the fixture fleet at chip_smoke.py's
        density spans at most 32 s of log time: 4 ticks, 65,520 rows at
        8,190 keys and two lags, under the cap even while the sink takes
        none (chip_smoke.py fails on any eviction)."""
        from .ingest.replay import ReplayDriver

        driver = ReplayDriver(self.parser)
        fed = driver.feed_dir(log_dir, chunk_bytes=1 << 16, between_chunks=self._settle)
        driver.finish()
        self.drain()
        return fed

    def _held_lines(self) -> int:
        """Lines that paused producers hold back from the broker."""
        return sum(
            q.buffer_count()
            for rt in (self.parser_rt, self.lead, self.sink_rt, self.jmx_rt)
            for q in rt.qm.queue_map.values()
            if q.type == "p"
        )

    def _settle(self, stall_s: float = 60.0) -> None:
        """Deliver until idle: no consumed queue holds a message, the worker
        has fed its intake to the device, and no paused producer holds
        lines. Raises if held lines stay put for ``stall_s``."""
        deadline = time.monotonic() + stall_s
        held = None
        while True:
            pumped = self.broker.pump()
            had_intake = self.worker.intake_pending
            self.worker.drain_intake()  # its ticks may enqueue more lines
            now_held = self._held_lines()
            if not (pumped or had_intake or now_held):
                return
            if pumped or had_intake or held is None or now_held < held:
                deadline = time.monotonic() + stall_s
            elif time.monotonic() > deadline:
                raise RuntimeError(f"standalone pipeline stalled: {now_held} lines "
                                   f"held by paused producers for {stall_s:.0f} s")
            else:
                time.sleep(0.001)  # the pump thread may be delivering
            held = now_held

    def drain(self) -> None:
        """Pump until quiescent, flush device + sink state (test/replay aid)."""
        self._settle()
        self.worker.driver.flush()
        while self.broker.pump():
            pass
        self.writer.process_all()

    def run_forever(self) -> None:
        self.lead.logger.info("Standalone pipeline running (single process, memory broker)")
        self.lead.run_forever()

    def shutdown(self) -> None:
        if self._closed:
            return
        self._closed = True
        # stop every runtime's timer threads (resume-save, intake-stats,
        # alert senders) and config watchers FIRST: runtime.exit() is a
        # process-exit path, so without this the daemon timers would keep
        # firing into torn-down state (closed log handlers, removed tmp dirs)
        for rt in (self.jmx_rt, self.parser_rt, self.sink_rt, self.lead):
            rt.stop_timers()
        self._run_exit_handlers(self.jmx_rt, self.parser_rt)
        # the parser's exit flush reaches the device before the worker saves
        # its state, and the worker's last rows reach the sink before it
        # closes: with the pump thread alone, either could race its save
        while self.broker.pump():
            pass
        self.worker.drain_intake()
        self.worker.shutdown()
        while self.broker.pump():
            pass
        self._run_exit_handlers(self.sink_rt)
        self.broker.stop()

    @staticmethod
    def _run_exit_handlers(*runtimes) -> None:
        for rt in runtimes:
            for handler in reversed(rt._exit_handlers):
                try:
                    handler()
                except Exception as e:
                    rt.logger.error(f"Exit handler error: {e}")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run the full pipeline in one process")
    ap.add_argument("--config", default=None)
    ap.add_argument("--replay", help="replay a directory of logs, drain, then exit")
    ap.add_argument("--no-tail", action="store_true")
    args = ap.parse_args(argv)

    pipe = StandalonePipeline(config_path=args.config, tail=not (args.replay or args.no_tail))
    if args.replay:
        fed = pipe.replay(args.replay)
        pipe.lead.logger.info(f"Replay complete: {fed} lines")
        pipe.shutdown()
        return 0
    pipe.run_forever()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
