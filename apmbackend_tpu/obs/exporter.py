"""Per-module telemetry HTTP server: /metrics, /healthz, /profile.

One daemon thread per module process (stdlib ThreadingHTTPServer — the
same no-new-deps discipline as the rest of the transport), started by
ModuleRuntime when the module's config carries a ``metricsPort``
(0 = ephemeral, for tests and colocated fleets; the bound port is
exposed as :attr:`TelemetryServer.port`). Routes:

- ``GET /metrics`` — the process registry in Prometheus text format
  (content type ``text/plain; version=0.0.4``): Grafana/Prometheus get a
  scrape target exactly like the reference's dashboards had.
- ``GET /healthz`` — JSON from registered health providers (tick
  liveness, emission backlog, device presence, child fleet state...).
  200 when every provider reports ``ok``, 503 otherwise — load-balancer
  and supervisor friendly.
- ``GET /profile?ms=500`` — on-demand capture: a jax.profiler trace of
  ``ms`` milliseconds into a timestamped directory (TensorBoard/perfetto
  readable) plus a heap snapshot via utils.profiling — the live
  "attach the inspector" affordance, now one curl away. Captures are
  serialized process-wide (jax.profiler is a process-global singleton):
  a second concurrent request — even against another TelemetryServer in
  the same process — gets 409 instead of racing two traces.
- ``GET /trace[?trace_id=&n=]`` — recent spans from the process trace
  ring (obs.trace), JSON; the per-module half of distributed traces (the
  manager's own ``/trace`` stitches across children by trace_id).
- ``GET /attrib`` — the wall-clock attribution plane (obs.attrib): the
  per-stage busy/blocked/idle table, time-weighted occupancies, and the
  critical-path bottleneck verdict (the manager's ``/attrib`` merges
  across children).
- ``GET /decisions[?trace_id=&n=]`` — recent alert decision records
  (obs.decisions): why each page fired, resolvable by trace_id.
- ``GET /flight?reason=...`` — on-demand flight-recorder bundle when the
  module runs one (obs.flight); the manager's watchdog requests this
  from a wedged child right before force-restarting it. A degraded
  /healthz also dumps a bundle (rate-limited).
- ``GET /metrics?exemplars=1`` — OpenMetrics-style exposition with
  histogram bucket exemplars (``# {trace_id="..."} value ts``).
- extra routes via :meth:`add_route` (the manager mounts ``/fleet``).
  Registered routes OVERRIDE the built-ins at the same path — the
  manager uses this to replace its per-process ``/query`` ``/trace``
  ``/decisions`` ``/attrib`` with the fleet-wide query plane
  (obs.queryplane), which scatter-gathers across children and falls
  back to the recorder store for dead shards.

Health providers and routes are plain callables so modules register
without this module importing them (no cycle into pipeline/runtime).
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from .registry import MetricsRegistry, get_registry

PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"
OPENMETRICS_CONTENT_TYPE = "application/openmetrics-text; version=1.0.0; charset=utf-8"

# jax.profiler is a process-global singleton: captures must serialize across
# every TelemetryServer in the process, not per instance (two modules'
# exporters in one standalone process used to race start_trace/stop_trace)
_profile_capture_lock = threading.Lock()

# live exporter count: single-process topologies (standalone) start ONE
# exporter on the lead runtime while satellites share the process registry —
# they gate their collector registration on this instead of owning a server
_active = 0
_active_lock = threading.Lock()


def telemetry_active() -> bool:
    """True while any TelemetryServer in this process is serving."""
    return _active > 0


class TelemetryServer:
    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        *,
        port: int = 0,
        host: str = "127.0.0.1",
        profile_dir: str = "logs",
        module: str = "apm",
        logger=None,
    ):
        self.registry = registry if registry is not None else get_registry()
        self._requested_port = port
        self.host = host
        self.profile_dir = profile_dir
        self.module = module
        self.logger = logger
        self.port: Optional[int] = None
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._health: Dict[str, Callable[[], dict]] = {}
        self._routes: Dict[str, Callable[[dict], Tuple[int, str, str]]] = {}
        # the module's FlightRecorder when it runs one (ModuleRuntime wires
        # it); serves /flight and the degraded-healthz auto-dump
        self.flight = None

    # -- registration ---------------------------------------------------------
    def add_health(self, name: str, fn: Callable[[], dict]) -> None:
        """``fn() -> dict``; an ``"ok": False`` key degrades /healthz to 503."""
        self._health[name] = fn

    def add_route(self, path: str, fn: Callable[[dict], Tuple[int, str, str]]) -> None:
        """``fn(query) -> (status, content_type, body)`` for extra GET paths."""
        self._routes[path] = fn

    # -- handlers -------------------------------------------------------------
    def _handle_metrics(self, query) -> Tuple[int, str, str]:
        if query.get("exemplars"):
            # OpenMetrics-style exposition: bucket lines carry trace_id
            # exemplars linking the latency histogram back to /trace
            return (
                200,
                OPENMETRICS_CONTENT_TYPE,
                self.registry.render(exemplars=True) + "# EOF\n",
            )
        return 200, PROM_CONTENT_TYPE, self.registry.render()

    def _handle_healthz(self, _query) -> Tuple[int, str, str]:
        body = {"module": self.module, "ts": time.time()}
        ok = True
        for name, fn in list(self._health.items()):
            try:
                section = fn() or {}
            except Exception as e:  # a broken probe IS a health failure
                section = {"ok": False, "error": repr(e)}
            if section.get("ok") is False:
                ok = False
            body[name] = section
        body["status"] = "ok" if ok else "degraded"
        if not ok and self.flight is not None:
            # degradation is a flight-recorder trigger; rate-limited inside
            # dump() so a flapping probe cannot churn the bundle directory
            try:
                bundle = self.flight.dump("healthz_degraded")
                if bundle:
                    body["flight_bundle"] = bundle
            except Exception:
                pass
        return (200 if ok else 503), "application/json", json.dumps(body, indent=1)

    def _handle_trace(self, query) -> Tuple[int, str, str]:
        from .trace import get_tracer

        trace_id = (query.get("trace_id") or [None])[0]
        try:
            n = max(1, min(int((query.get("n") or ["256"])[0]), 4096))
        except (TypeError, ValueError):
            return 400, "application/json", json.dumps({"error": "bad n parameter"})
        tracer = get_tracer()
        spans = tracer.ring.spans(trace_id=trace_id, n=n)
        body = {
            "module": self.module,
            "sample_rate": tracer.rate,
            "count": len(spans),
            "spans": spans,
        }
        return 200, "application/json", json.dumps(body, indent=1, default=repr)

    def _handle_attrib(self, _query) -> Tuple[int, str, str]:
        from .attrib import get_attrib

        return 200, "application/json", json.dumps(
            get_attrib().snapshot(), indent=1, default=repr
        )

    def _handle_decisions(self, query) -> Tuple[int, str, str]:
        from .decisions import get_decisions

        trace_id = (query.get("trace_id") or [None])[0]
        try:
            n = max(1, min(int((query.get("n") or ["128"])[0]), 4096))
        except (TypeError, ValueError):
            return 400, "application/json", json.dumps({"error": "bad n parameter"})
        ring = get_decisions()
        records = ring.recent(n, trace_id=trace_id)
        body = {
            "module": self.module,
            "total": ring.total,
            "count": len(records),
            "decisions": records,
        }
        return 200, "application/json", json.dumps(body, indent=1, default=repr)

    def _handle_flight(self, query) -> Tuple[int, str, str]:
        if self.flight is None:
            return 404, "application/json", json.dumps(
                {"error": "flight recorder not configured (observability.flightDir)"}
            )
        reason = (query.get("reason") or ["on_demand"])[0][:64]
        try:
            path = self.flight.dump(reason, force=True)
        except Exception as e:
            return 500, "application/json", json.dumps({"error": repr(e)})
        return 200, "application/json", json.dumps({"module": self.module, "bundle": path})

    def _handle_profile(self, query) -> Tuple[int, str, str]:
        """Capture a bounded device trace + heap snapshot; serialized
        process-wide so two concurrent curls (or two exporters in one
        process) cannot interleave jax.profiler start/stop or land two
        captures in the same directory."""
        try:
            ms = max(1, min(int(query.get("ms", ["500"])[0]), 60_000))
        except (TypeError, ValueError):
            return 400, "application/json", json.dumps({"error": "bad ms parameter"})
        if not _profile_capture_lock.acquire(blocking=False):
            return 409, "application/json", json.dumps({"error": "profile capture already running"})
        try:
            import os

            from ..utils.profiling import NO_DEVICE, heap_snapshot, loaded_jax

            # pid + uuid: two captures in the same second (or from two
            # processes sharing a log dir) must not collide on one directory
            import uuid

            stamp = f"{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}-{uuid.uuid4().hex[:6]}"
            trace_dir = os.path.join(self.profile_dir, f"profile-{self.module}-{stamp}")
            result = {"module": self.module, "ms": ms}
            jax = loaded_jax()
            if jax is None:
                result["trace_error"] = NO_DEVICE
            else:
                try:
                    jax.profiler.start_trace(trace_dir)
                    time.sleep(ms / 1000.0)
                    jax.profiler.stop_trace()
                    result["trace_dir"] = trace_dir
                except Exception as e:  # profiler unavailable: still return
                    # the heap side — diagnostics degrade, never 500
                    result["trace_error"] = repr(e)
            result["heap_snapshot"] = heap_snapshot(
                self.profile_dir, f"{self.module}-profile", logger=self.logger
            )
            status = 200 if ("trace_dir" in result or result["heap_snapshot"]) else 503
            return status, "application/json", json.dumps(result, indent=1)
        finally:
            _profile_capture_lock.release()

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> int:
        outer = self

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 - http.server API
                parsed = urlparse(self.path)
                route = {
                    "/metrics": outer._handle_metrics,
                    "/healthz": outer._handle_healthz,
                    "/profile": outer._handle_profile,
                    "/trace": outer._handle_trace,
                    "/attrib": outer._handle_attrib,
                    "/decisions": outer._handle_decisions,
                    "/flight": outer._handle_flight,
                    **outer._routes,
                }.get(parsed.path)
                if route is None:
                    self.send_error(404)
                    return
                try:
                    status, ctype, body = route(parse_qs(parsed.query))
                except Exception as e:
                    status, ctype, body = 500, "text/plain", f"handler error: {e!r}"
                data = body.encode("utf-8")
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def log_message(self, *_args):  # scrapes must not spam the module log
                pass

        self._server = ThreadingHTTPServer((self.host, self._requested_port), _Handler)
        self._server.daemon_threads = True
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name=f"telemetry-{self.module}",
            daemon=True,
        )
        self._thread.start()
        global _active
        with _active_lock:
            _active += 1
        if self.logger:
            self.logger.info(
                f"Telemetry exporter listening on http://{self.host}:{self.port} "
                f"(/metrics /healthz /profile /trace /attrib /decisions /flight)"
            )
        return self.port

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self) -> None:
        global _active
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
            with _active_lock:
                _active -= 1
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
