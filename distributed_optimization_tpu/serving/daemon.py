"""Stdlib-only HTTP daemon over ``SimulationService`` (docs/SERVING.md).

``python -m distributed_optimization_tpu.serve`` boots it. No new runtime
dependencies: ``http.server`` + JSON lines. Protocol (all bodies JSON;
manifests are STRICT JSON via the telemetry layer's non-finite sentinel
encoding, so ``jq``/``JSON.parse`` read them even for divergent runs):

- ``POST /v1/submit``  — body: an ExperimentConfig field object (or
  ``{"config": {...}}``). 202 → ``{"id", "status", "queue_depth"}``.
  Malformed JSON / unknown fields / invalid configs → 400 with
  ``{"error", "detail"}`` carrying the config validation message; the
  request never enters the queue and in-flight work is untouched.
- ``POST /v1/run``     — submit AND wait; streams the finished request's
  RunTrace manifest back as one JSONL line (the curl one-liner in
  docs/SERVING.md). ``?timeout=S`` bounds the wait (default 300).
- ``GET /v1/result/<id>[?timeout=S]`` — the manifest once done (200), a
  status object while queued/running (202), 404 for unknown ids, 500
  body with the failure message for failed requests.
- ``GET /v1/progress/<id>[?timeout=S&after=SEQ]`` — LIVE streaming JSONL
  (ISSUE-10): one line per heartbeat (lifecycle events + the backend's
  per-chunk progress — iteration, wall seconds, current gap/consensus,
  live B̂, staleness quantiles on async runs), replayed from ``after``
  and followed until the request finishes or ``timeout`` (default 300 s)
  elapses. The response has no Content-Length and closes when the
  stream ends — read it line by line (``curl -N``).
- ``GET /v1/status``   — service stats: queue depth, cohort/coalescing
  counters, executable-cache hits/misses/compile-seconds-saved (counter
  blocks ALWAYS present, zeros before any work), and the bounded
  last-K finished-request history.
- ``GET /metrics``     — the process metrics registry in Prometheus text
  exposition format (cache, coalescer, queue, progress, async-staleness
  families; one consistent snapshot per scrape).
- ``POST /v1/shutdown`` — drain nothing, stop accepting, exit cleanly.
  ``?drain=1[&deadline=S]`` (ISSUE-15) drains gracefully instead: new
  submissions get 503 while queued + in-flight cohorts finish (bounded
  by the deadline, default 30 s), then the daemon exits; the response
  reports ``drained: true/false``.

Admission (ISSUE-15): the wrapped submit form ``{"config": {...},
"tenant": "acme", "priority": "high"}`` tags the request for the
weighted-fair scheduler; per-tenant caps shed with 429 + a machine-
readable reason. Bare config bodies run as tenant "default".
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

from distributed_optimization_tpu.log import get_logger
from distributed_optimization_tpu.serving.service import (
    DONE,
    FAILED,
    DrainingError,
    QueueFullError,
    ServingError,
    ServingOptions,
    SimulationService,
)

_log = get_logger("serving.daemon")

DEFAULT_PORT = 8421
DEFAULT_RUN_TIMEOUT_S = 300.0
MAX_BODY_BYTES = 1_000_000  # a config object is ~1 KB; bound hostile bodies
# Per-connection socket timeout (ISSUE-12 satellite). Without one, a
# client that connects and never completes a request — or opens a
# streaming response and never reads — pins its handler thread FOREVER
# (rfile.readline / wfile.write block indefinitely), and a handful of
# stalled clients exhaust the threaded server. The timeout bounds every
# blocking socket op; on expiry the read loop closes the connection and
# the streaming writers bail out through their OSError handling. It must
# comfortably exceed the heartbeat cadence so live progress streams are
# never cut between events.
DEFAULT_SOCKET_TIMEOUT_S = 75.0


def _strict_json(obj) -> bytes:
    from distributed_optimization_tpu.telemetry import _encode_nonfinite

    return (
        json.dumps(_encode_nonfinite(obj), sort_keys=True, allow_nan=False)
        + "\n"
    ).encode()


class _Handler(BaseHTTPRequestHandler):
    # The service lives on the server object (one per daemon).
    server: "_Server"

    protocol_version = "HTTP/1.1"

    def setup(self) -> None:
        super().setup()
        timeout = self.server.socket_timeout_s
        if timeout and timeout > 0:
            # Bounds EVERY blocking op on this connection (request reads,
            # response and stream writes); http.server's read loop maps
            # the read-side expiry to close_connection itself.
            self.connection.settimeout(timeout)

    def handle(self) -> None:
        try:
            super().handle()
        except (TimeoutError, ConnectionError, OSError) as e:
            # A write-side stall (client stopped reading) surfaces here
            # once the kernel buffer fills and the socket timeout fires:
            # log one debug line instead of a traceback; socketserver
            # tears the connection down on return and the handler thread
            # is reclaimed.
            _log.debug(
                "dropping stalled/broken connection from %s: %s",
                self.client_address, e,
            )

    def log_message(self, fmt, *args):  # route http.server chatter to our log
        _log.debug("%s " + fmt, self.address_string(), *args)

    # ------------------------------------------------------------- helpers
    def _send(self, code: int, payload: dict, *, jsonl: bool = False) -> None:
        body = _strict_json(payload)
        self.send_response(code)
        self.send_header(
            "Content-Type",
            "application/x-ndjson" if jsonl else "application/json",
        )
        self.send_header("Content-Length", str(len(body)))
        if self.close_connection:
            # A route decided the connection cannot be reused (e.g. an
            # oversized body it refused to read); say so on the wire.
            self.send_header("Connection", "close")
        self.end_headers()
        self.wfile.write(body)

    def _error(self, code: int, error: str, detail: str = "") -> None:
        self._send(code, {"error": error, "detail": detail})

    def _read_config(self) -> Optional[tuple]:
        """Parse the request body into ``(config_dict, tenant, priority)``,
        or answer 400 and return None. Structured errors, never a dead
        connection. The admission fields ride the WRAPPED form only —
        ``{"config": {...}, "tenant": "...", "priority": "..."}`` — so a
        bare config object stays exactly the PR-7 protocol."""
        try:
            length = int(self.headers.get("Content-Length", "0"))
        except ValueError:
            length = 0
        if length <= 0:
            self._error(400, "empty_body",
                        "POST a JSON ExperimentConfig object")
            return None
        if length > MAX_BODY_BYTES:
            # Refusing to READ the oversized body would desync a
            # keep-alive connection (the unread bytes would parse as the
            # next request line), so this rejection also closes it.
            self.close_connection = True
            self._error(400, "body_too_large",
                        f"config bodies are capped at {MAX_BODY_BYTES} bytes")
            return None
        raw = self.rfile.read(length)
        try:
            payload = json.loads(raw)
        except json.JSONDecodeError as e:
            self._error(400, "malformed_json", str(e))
            return None
        tenant = priority = None
        if isinstance(payload, dict) and isinstance(
            payload.get("config"), dict
        ):
            tenant = payload.get("tenant")
            priority = payload.get("priority")
            payload = payload["config"]
        if not isinstance(payload, dict):
            self._error(
                400, "invalid_request",
                "body must be a JSON object of ExperimentConfig fields "
                "(optionally wrapped as {\"config\": {...}, "
                "\"tenant\": ..., \"priority\": ...})",
            )
            return None
        return payload, tenant, priority

    def _query(self) -> dict:
        return parse_qs(urlparse(self.path).query)

    def _timeout(self, default: float) -> float:
        q = self._query().get("timeout")
        try:
            return float(q[0]) if q else default
        except ValueError:
            return default

    def _respond_request(self, req) -> None:
        if req.status == DONE:
            self._send(200, req.manifest, jsonl=True)
        elif req.status == FAILED:
            self._send(500, {
                **req.status_dict(),
                "error": "run_failed",
                "detail": req.error,
            })
        else:
            self._send(202, {
                **req.status_dict(),
                "queue_depth": self.server.service.queue_depth(),
            })

    # ------------------------------------------------------------- routes
    def do_POST(self) -> None:  # noqa: N802 (http.server API)
        path = urlparse(self.path).path.rstrip("/")
        service = self.server.service
        if path == "/v1/shutdown":
            q = self._query()
            if q.get("drain", ["0"])[0] in ("1", "true", "yes"):
                # Graceful drain (ISSUE-15 satellite): refuse new
                # submissions (503), finish queued + in-flight cohorts
                # within the deadline, then exit. The response reports
                # whether the drain actually emptied the service so
                # operators can tell a clean stop from a deadline kill.
                try:
                    deadline = float(q.get("deadline", ["30"])[0])
                except ValueError:
                    deadline = 30.0
                service.begin_drain()
                drained = service.wait_drained(timeout=deadline)
                self._send(200, {
                    "status": "shutting_down",
                    "drained": drained,
                })
            else:
                # The PR-7 default, unchanged: drain nothing, stop now.
                self._send(200, {"status": "shutting_down"})
            self.server.initiate_shutdown()
            return
        if path not in ("/v1/submit", "/v1/run"):
            self._error(404, "unknown_endpoint", path)
            return
        parsed = self._read_config()
        if parsed is None:
            return
        payload, tenant, priority = parsed
        try:
            request_id = service.submit(
                payload, tenant=tenant, priority=priority
            )
        except QueueFullError as e:
            # Backpressure is retryable server state, not a bad request —
            # a distinct status so clients can implement retry without
            # string-matching the detail. Shed-load rejections carry the
            # admission reason + tenant for dashboards and tests.
            self._send(429, {
                "error": "queue_full",
                "detail": str(e),
                "reason": e.reason,
                "tenant": e.tenant,
            })
            return
        except DrainingError as e:
            # Retryable by the client contract — the drain precedes a
            # restart that will take the retry. Must be checked before
            # ServingError (it IS one).
            self._error(503, "draining", str(e))
            return
        except ServingError as e:
            # The structured rejection (config validation message included)
            # — a poison submission answers 400 and touches nothing else.
            self._error(400, "invalid_config", str(e))
            return
        if path == "/v1/submit":
            self._send(202, {
                "id": request_id,
                "status": "queued",
                "queue_depth": service.queue_depth(),
            })
            return
        try:
            req = service.result(
                request_id, timeout=self._timeout(DEFAULT_RUN_TIMEOUT_S)
            )
        except TimeoutError as e:
            self._error(504, "timeout", str(e))
            return
        self._respond_request(req)

    def _stream_progress(self, req) -> None:
        """Stream a request's heartbeats as JSONL until it finishes (or
        the timeout elapses). No Content-Length — the body is terminated
        by connection close, so a client reads lines as they arrive
        (``curl -N``); buffered events replay first (``?after=SEQ``
        resumes a reconnect past what it already saw)."""
        q = self._query()
        try:
            after = int(q["after"][0]) if "after" in q else -1
        except ValueError:
            after = -1
        timeout = self._timeout(DEFAULT_RUN_TIMEOUT_S)
        self.close_connection = True
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Connection", "close")
        self.end_headers()
        try:
            for payload in req.progress.follow(after, timeout=timeout):
                self.wfile.write(_strict_json(payload))
                self.wfile.flush()
        except (TimeoutError, ConnectionError, OSError):
            # Client went away mid-stream, or stopped reading long enough
            # for the connection's socket timeout to fire (a stalled
            # reader must not pin this streaming thread): nothing to
            # clean up, the stream just ends.
            pass

    def do_GET(self) -> None:  # noqa: N802 (http.server API)
        path = urlparse(self.path).path.rstrip("/")
        service = self.server.service
        if path == "/v1/status":
            self._send(200, {"status": "serving", **service.stats()})
            return
        if path == "/metrics":
            from distributed_optimization_tpu.observability.metrics_registry import (  # noqa: E501
                metrics_registry,
            )

            body = metrics_registry().render().encode()
            self.send_response(200)
            self.send_header(
                "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
            )
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if path.startswith("/v1/progress/"):
            request_id = path[len("/v1/progress/"):]
            try:
                req = service.get(request_id)
            except KeyError:
                self._error(404, "unknown_request", request_id)
                return
            self._stream_progress(req)
            return
        if path.startswith("/v1/result/"):
            request_id = path[len("/v1/result/"):]
            try:
                req = service.get(request_id)
            except KeyError:
                self._error(404, "unknown_request", request_id)
                return
            timeout = self._timeout(0.0)
            if timeout > 0:
                req.done.wait(timeout)
            self._respond_request(req)
            return
        self._error(404, "unknown_endpoint", path)


class _Server(ThreadingHTTPServer):
    daemon_threads = True
    # Serving requests block for seconds; keep the accept queue generous.
    request_queue_size = 32

    def __init__(
        self, addr, service: SimulationService,
        socket_timeout_s: float = DEFAULT_SOCKET_TIMEOUT_S,
    ):
        super().__init__(addr, _Handler)
        self.service = service
        self.socket_timeout_s = socket_timeout_s

    def initiate_shutdown(self) -> None:
        # shutdown() must not run on a handler thread (it joins the serve
        # loop); hand it to a one-shot thread.
        threading.Thread(target=self.shutdown, daemon=True).start()


class ServingDaemon:
    """The HTTP daemon: owns a ``SimulationService`` (scheduler started)
    and a threading HTTP server. ``serve_forever()`` blocks (the CLI
    mode); ``start()``/``stop()`` run it on a background thread (tests,
    ``make serve-smoke``)."""

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = DEFAULT_PORT,
        options: Optional[ServingOptions] = None,
        *,
        service: Optional[SimulationService] = None,
        socket_timeout_s: float = DEFAULT_SOCKET_TIMEOUT_S,
    ):
        self.service = service or SimulationService(options)
        self._server = _Server(
            (host, port), self.service, socket_timeout_s=socket_timeout_s,
        )
        self._thread: Optional[threading.Thread] = None
        # Optional fleet autoscaler (ISSUE-16): assigned before
        # serve_forever()/start(), started once the service is up, and
        # stopped by service.close() (which owns the ordering: autoscaler
        # first, then the pool it scales).
        self.autoscaler = None

    @property
    def address(self) -> tuple:
        return self._server.server_address[:2]

    @property
    def url(self) -> str:
        host, port = self.address
        return f"http://{host}:{port}"

    def serve_forever(self) -> None:
        self.service.start()
        if self.autoscaler is not None:
            self.autoscaler.start()
        host, port = self.address
        _log.info("simulation service listening on http://%s:%s", host, port)
        try:
            self._server.serve_forever(poll_interval=0.2)
        finally:
            self.close()

    def start(self) -> None:
        self.service.start()
        if self.autoscaler is not None:
            self.autoscaler.start()
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            kwargs={"poll_interval": 0.1},
            name="serving-daemon", daemon=True,
        )
        self._thread.start()

    def stop(self) -> None:
        self._server.shutdown()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        self.close()

    def close(self) -> None:
        self.service.close()
        self._server.server_close()


def main(argv=None) -> int:
    """``python -m distributed_optimization_tpu.serve`` entry point."""
    import argparse

    from distributed_optimization_tpu.log import configure as configure_logging

    p = argparse.ArgumentParser(
        prog="distributed_optimization_tpu.serve",
        description=(
            "Simulation-as-a-service daemon: POST ExperimentConfig JSON, "
            "stream RunTrace manifests back; structurally identical "
            "concurrent requests coalesce into one batched XLA program and "
            "repeat programs reuse cached executables (docs/SERVING.md)."
        ),
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=DEFAULT_PORT,
                   help=f"TCP port (default {DEFAULT_PORT}; 0 = ephemeral)")
    p.add_argument("--window-ms", type=float, default=50.0,
                   help="coalescing wait window after work arrives "
                        "(latency traded for batching opportunity)")
    p.add_argument("--max-cohort", type=int, default=32,
                   help="replica-axis cap per coalesced run_batch call")
    p.add_argument("--max-pending", type=int, default=1024,
                   help="queue bound; submits beyond it get a 429")
    p.add_argument("--max-pending-per-tenant", type=int, default=None,
                   help="per-tenant queue depth cap; a tenant at its cap "
                        "gets shed-load 429s (reason=tenant_cap) while "
                        "other tenants keep submitting")
    p.add_argument("--cut-budget", type=int, default=None,
                   help="max requests per scheduler cut (weighted-fair "
                        "across tenants); default: everything pending")
    p.add_argument("--workers", type=int, default=0,
                   help="worker processes for cohort execution (0 = run "
                        "on the scheduler thread); the persistent store "
                        "is their shared warm tier")
    p.add_argument("--store", default=None,
                   help="persistent executable store directory: compiled "
                        "programs are serialized there and reloaded "
                        "across daemon restarts (0 compile seconds for "
                        "previously-served structural classes)")
    p.add_argument("--fleet", action="store_true",
                   help="enable the self-healing fleet remediation "
                        "policies (divergence halt+requeue+quarantine, "
                        "store-corruption quarantine, dead-worker "
                        "respawn attribution); see docs/SERVING.md")
    p.add_argument("--fleet-incidents", default=None, metavar="PATH",
                   help="append remediated incidents (with their "
                        "remediation blocks) to this JSONL file for "
                        "`observatory incidents --remediated`; implies "
                        "--fleet")
    p.add_argument("--quarantine-ttl", type=float, default=300.0,
                   help="seconds a (tenant, structural class) pair stays "
                        "quarantined after a divergence incident")
    p.add_argument("--autoscale-max", type=int, default=None,
                   help="enable the queue-driven autoscaler with this "
                        "worker ceiling (requires --workers >= 1; the "
                        "initial --workers count is the starting fleet)")
    p.add_argument("--autoscale-min", type=int, default=1,
                   help="autoscaler worker floor (default 1)")
    p.add_argument("--port-file", default=None,
                   help="write the bound host:port here once listening "
                        "(for --port 0 orchestration: benches, smokes)")
    p.add_argument("--socket-timeout", type=float,
                   default=DEFAULT_SOCKET_TIMEOUT_S,
                   help="per-connection socket timeout in seconds; a "
                        "client that stalls a read or write longer than "
                        "this is dropped so it cannot pin a handler "
                        "thread (0 disables)")
    p.add_argument("--platform", choices=("tpu", "cpu", "auto"),
                   default="auto",
                   help="force the JAX platform before first use")
    p.add_argument("-v", "--verbose", action="store_true")
    p.add_argument("-q", "--quiet", action="store_true")
    args = p.parse_args(argv)

    configure_logging(1 if args.verbose else (-1 if args.quiet else 0))
    if args.platform != "auto":
        import os as os_mod

        # The env form (not jax.config.update) so spawned worker
        # processes inherit the pin before THEIR jax initializes.
        os_mod.environ["JAX_PLATFORMS"] = args.platform
        import jax

        jax.config.update("jax_platforms", args.platform)
    from distributed_optimization_tpu.runtime import configure_compile_cache

    configure_compile_cache()
    if args.store:
        # The env var is the single wiring point for the persistent
        # store: the parent's process cache attaches it on first use,
        # and spawned workers inherit it — one shared warm tier.
        import os as os_mod

        os_mod.environ["DOPT_EXEC_STORE"] = args.store

    daemon = ServingDaemon(
        args.host, args.port,
        ServingOptions(
            window_s=args.window_ms / 1000.0,
            max_cohort=args.max_cohort,
            max_pending=args.max_pending,
            max_pending_per_tenant=args.max_pending_per_tenant,
            cut_budget=args.cut_budget,
            workers=args.workers,
        ),
        socket_timeout_s=args.socket_timeout,
    )
    if args.fleet or args.fleet_incidents:
        from distributed_optimization_tpu.serving.fleet import (
            FleetOptions,
            RemediationEngine,
        )

        RemediationEngine(FleetOptions(
            quarantine_ttl_s=args.quarantine_ttl,
            incident_log=args.fleet_incidents,
        )).attach(daemon.service)
    if args.autoscale_max is not None:
        if args.workers < 1:
            p.error("--autoscale-max requires --workers >= 1 "
                    "(an in-process service has nothing to scale)")
        from distributed_optimization_tpu.serving.fleet import (
            AutoscaleOptions,
            QueueAutoscaler,
        )

        daemon.autoscaler = QueueAutoscaler(
            daemon.service,
            AutoscaleOptions(
                min_workers=args.autoscale_min,
                max_workers=args.autoscale_max,
            ),
        )
    if args.port_file:
        host, port = daemon.address
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(f"{host}:{port}\n")
        import os as os_mod

        os_mod.replace(tmp, args.port_file)  # atomic: readers never see ""
    try:
        daemon.serve_forever()
    except KeyboardInterrupt:
        daemon.close()
    return 0
