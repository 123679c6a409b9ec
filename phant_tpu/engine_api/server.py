"""Threaded HTTP JSON-RPC server for the Engine API.

Equivalent surface to the reference's httpz server wiring (reference:
src/main.zig:143-149: POST / routed to engineAPIHandler with the
*Blockchain as per-request context). Uses the stdlib ThreadingHTTPServer.

Request execution goes through the continuous-batching scheduler
(phant_tpu/serving/) instead of the old global execution lock:

* state-mutating methods (`engine_newPayload*`, `engine_forkchoiceUpdated*`)
  run as SERIAL jobs on the scheduler's single executor thread — mutation
  stays exclusive (the reference is effectively serial there too) without
  a mutex held across the whole request;
* `engine_executeStatelessPayloadV1` runs CONCURRENTLY on the handler
  threads (stateless execution shares nothing), and its witness
  verification coalesces with other in-flight requests into one
  engine/device `verify_batch` dispatch via the scheduler's batch
  assembler (stateless.admit_witness) — with `--sched-mesh N`
  those dispatches fan out over N device-pinned executors
  (serving/mesh_exec.py), and `/healthz` carries the per-device lane
  state under `scheduler.mesh` (any dead lane turns the probe 503
  exactly like a dead executor: routed batches would never complete);
* scheduler rejections map to distinct JSON-RPC errors: queue full /
  tenant quota / evicted -32050, deadline expired -32051, executor down
  -32052 — all HTTP 503, counted under `sched.rejected{reason=,tenant=}`;
* multi-tenant QoS (phant_tpu/serving/qos.py): `X-Phant-Tenant` names the
  per-client admission lane (quota + weighted fair dequeue) and
  `X-Phant-Priority: head` marks head-of-chain work that preempts
  backfill — state-mutating methods are always head class;
* slow-loris tolerance: every accepted connection carries a socket
  read/write deadline (PHANT_HTTP_TIMEOUT_S, default 30s) so a client
  that stalls mid-headers, mid-body, or mid-read frees the handler
  thread; the stall is counted in `engine_api.client_disconnects`.

Observability surface: `GET /metrics` serves the process metrics registry
as Prometheus text exposition (histogram families additionally carry
derived bucket-interpolated p50/p99 gauges), `GET /healthz` a JSON
liveness probe that includes the scheduler state (queue depth, executor
liveness) and turns 503 when the executor has
died; `GET /debug/flight` serves the obs flight recorder's ring (recent
spans / errors / scheduler transitions) live, `GET /debug/slow` the
SLO-exemplar ring (obs/critpath.py — full span trees of requests that
blew `--slo-budget-ms`), `GET /debug/timeline?window=S` the unified
tail-sampled timeline as Perfetto-loadable Chrome-trace JSON
(obs/timeline.py — requests with their phases at measured offsets and
lane batches on one time axis), `POST /debug/profile?seconds=T` grabs an
on-demand, single-flight-guarded `jax_profile` capture into
`--profile-dir` (obs/profiler.py; the profiler's Python tracer off, its
host tracer at level 1, so the capture holds the program's own `phant/`
events and does not slow the server it looks at), and the first `/healthz` flip to 503
auto-dumps the flight ring to `build/flight/` (phant_tpu/obs/). Every POST runs inside its own trace
context — the `trace_id` rides the scheduler jobs and span records the
request creates, and is echoed back in the `X-Phant-Trace` response
header — and is counted, latency-histogrammed, and gauge-tracked in
flight (phant_tpu/utils/trace.py). `serve_metrics()` runs the same GET
endpoints standalone for `--metrics-port` deployments where the Engine API
port is CL-only."""

from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from phant_tpu.engine_api import handle_request
from phant_tpu.obs import critpath, flight, profiler, timeline
from phant_tpu.obs.flight import refresh_from_env as _refresh_flight_ring
from phant_tpu.serving import (
    LANE_PROGRAMS,
    PRIORITY_BACKFILL,
    PRIORITY_HEAD,
    SchedulerConfig,
    SchedulerError,
    VerificationScheduler,
    active_scheduler,
    boot_lanes,
    collector,
    current_priority,
    current_tenant,
    install,
    on_cpu,
    sanitize_tenant,
    tenant_context,
    uninstall,
)
from phant_tpu.utils.trace import (
    REQUEST_SECONDS_BUCKETS,
    current_span,
    current_trace_id,
    metrics,
    span,
    trace_context,
)

log = logging.getLogger("phant_tpu.engine_api")

_START_MONOTONIC = time.monotonic()

#: the front end's own phases of a POST (`engine_api.phase_seconds{phase=}`):
#: the marks `do_POST`/`_handle_post` set on the request's frame span, which
#: with verify_block's wall clock tile `engine_api.request_seconds`. `gate`
#: is the wait for a slot of the stateless gate: microseconds unless the
#: node is saturated, and then not to be read as decode time
FRONTEND_PHASES = ("read", "json", "gate", "decode", "reply")

#: methods that mutate Blockchain state and therefore run as serial jobs
#: on the scheduler's executor (everything else is read-only or stateless
#: and runs concurrently on the handler threads)
_SERIAL_METHOD_PREFIXES = ("engine_newPayload", "engine_forkchoiceUpdated")


def _http_timeout() -> float:
    """Socket read/write deadline per accepted connection
    (PHANT_HTTP_TIMEOUT_S, default 30; <=0 disables). A client that sends
    headers and then stalls — the slow-loris shape scripts/loadgen.py
    deliberately produces — must not pin a handler thread forever: the
    deadline frees the thread and the stall is counted in
    `engine_api.client_disconnects`. Read per connection so tests and the
    load harness can tighten it without rebinding the server."""
    return float(os.environ.get("PHANT_HTTP_TIMEOUT_S", "30"))


class _StatelessGate:
    """Bounded concurrency for `engine_executeStatelessPayloadV1`.

    The scheduler bounds QUEUED witness verifications, but the rest of a
    stateless execution (witness decode, EVM re-execution, root check)
    runs on the handler thread — and ThreadingHTTPServer spawns one per
    connection, so under open-loop overload the box accumulates hundreds
    of half-done executions that thrash each other into multi-second p99s
    while every one of them eventually "succeeds" (loadgen measured
    exactly this before the gate existed). Graceful degradation means
    refusing work the box cannot finish promptly: at most `limit`
    stateless executions run at once; a request that cannot get a slot
    within its class's patience sheds with the standard overload code
    (-32050, `sched.rejected{reason=saturated, tenant=...}`).

    Patience is the priority lever: backfill waits ~PHANT_HTTP_GATE_PATIENCE_S
    (default 0.5s — overload must shed fast, not stack), head-of-chain
    (`X-Phant-Priority: head`) waits 8x that before giving up. The serial
    mutation lane never passes through this gate at all (shed order:
    backfill first, never mutations)."""

    def __init__(self, limit: int, patience_s: float):
        self._sem = threading.Semaphore(limit) if limit > 0 else None
        self.limit = limit
        self.patience_s = patience_s

    def acquire(self, head: bool) -> bool:
        if self._sem is None:
            return True
        patience = self.patience_s * (8.0 if head else 1.0)
        return self._sem.acquire(timeout=patience)

    def release(self) -> None:
        if self._sem is not None:
            self._sem.release()


def _default_gate() -> _StatelessGate:
    limit = int(
        os.environ.get(
            "PHANT_HTTP_MAX_CONCURRENT", str(max(8, 4 * (os.cpu_count() or 2)))
        )
    )
    patience = float(os.environ.get("PHANT_HTTP_GATE_PATIENCE_S", "0.5"))
    return _StatelessGate(limit, patience)


#: the scheduler instance whose death already triggered a healthz-503 dump
#: (flip detection is per SCHEDULER, not per process: a later server's own
#: first 503 must still dump, and healthy scrapes clear the latch)
_healthz_dumped_for = None
_healthz_lock = threading.Lock()


def _healthz_payload() -> tuple:
    """(http_status, payload): liveness plus scheduler state. A dead
    scheduler executor means the node can no longer execute payloads, so
    the probe reports 503 — orchestrators must restart, not route — and
    the FIRST flip to 503 dumps the flight ring (the postmortem the
    restart would otherwise destroy)."""
    from phant_tpu.version import RELEASE, revision

    global _healthz_dumped_for
    from phant_tpu.commitment import active_scheme

    payload = {
        "status": "ok",
        "version": RELEASE,
        "revision": revision(),
        "uptime_s": round(time.monotonic() - _START_MONOTONIC, 1),
        # how state is committed on this node (--commitment): a CL pairing
        # with the wrong scheme sees every payload rejected on its state
        # root, so the probe names the scheme explicitly
        "commitment": active_scheme().name,
    }
    status = 200
    sched = active_scheduler()
    if sched is not None:
        st = sched.state()
        payload["scheduler"] = st
        if not st["executor_alive"]:
            payload["status"] = "unhealthy"
            status = 503
    # every debug-ring capacity in one place (the --flight-ring /
    # --timeline-* config surfaces echo back what actually took effect)
    payload["debug_rings"] = {
        "flight": flight.ring_capacity(),
        "slow": critpath.slow.capacity,
        "timeline": timeline.capacity(),
    }
    with _healthz_lock:
        if status == 503:
            flipped = sched is not _healthz_dumped_for
            _healthz_dumped_for = sched
        else:
            flipped = False
            _healthz_dumped_for = None
    if flipped:
        flight.dump("healthz_503")
    return status, payload


class _HTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a real listen backlog. The stdlib default
    (request_queue_size=5) turns overload into multi-second connect waits
    in the KERNEL accept queue — an invisible, unshed, unmeasured queue in
    front of all the admission control this package builds. A deep backlog
    moves the excess onto handler threads where the stateless gate and the
    scheduler shed it with explicit -32050s within their patience window."""

    request_queue_size = 256

    def service_actions(self) -> None:
        """Every poll interval of `serve_forever` and after every accepted
        connection: the one place that runs when nobody is waiting."""
        collector.idle_tick()


class _ObservableHandler(BaseHTTPRequestHandler):
    """Shared GET surface + disconnect-tolerant reply plumbing."""

    protocol_version = "HTTP/1.1"

    def setup(self) -> None:
        # socket read/write deadline BEFORE any rfile read: a stalled
        # client (slow-loris headers, never-arriving body, wedged reader)
        # raises TimeoutError out of the blocked call instead of pinning
        # this handler thread for the life of the process. The stdlib's
        # handle_one_request already closes the connection on that
        # TimeoutError; the do_POST body read counts it first.
        t = _http_timeout()
        self.timeout = t if t > 0 else None
        super().setup()

    def do_GET(self) -> None:  # noqa: N802 (stdlib API)
        path = self.path.split("?", 1)[0]
        if path == "/metrics":
            self._reply_raw(
                200,
                metrics.prometheus_text().encode(),
                "text/plain; version=0.0.4; charset=utf-8",
            )
        elif path == "/healthz":
            status, payload = _healthz_payload()
            self._reply(status, payload)
        elif path == "/debug/flight":
            # the live flight ring: what a postmortem dump would contain,
            # readable from a still-running server (default=str: span attrs
            # are caller-provided and may not all be JSON-native)
            self._reply_raw(
                200,
                json.dumps(flight.snapshot(), default=str).encode(),
                "application/json",
            )
        elif path == "/debug/timeline":
            # the unified timeline (obs/timeline.py): the last `window`
            # seconds of kept requests, lane batches and profiler
            # captures as Perfetto-loadable
            # Chrome-trace JSON — curl it straight into ui.perfetto.dev
            query = self.path.partition("?")[2]
            params = dict(
                p.split("=", 1) for p in query.split("&") if "=" in p
            )
            try:
                window = float(params.get("window", "60"))
            except ValueError:
                window = float("nan")
            if not math.isfinite(window) or window <= 0:
                self._reply(
                    400,
                    {"error": "window must be a positive number of seconds"},
                )
            else:
                self._reply_raw(
                    200,
                    json.dumps(timeline.export(window), default=str).encode(),
                    "application/json",
                )
        elif path == "/debug/slow":
            # SLO-busting exemplars (obs/critpath.py): full span trees +
            # critical-path breakdowns of every request that blew
            # --slo-budget-ms (or a per-phase env budget) — the metric
            # says THAT it was slow, this ring says WHY
            self._reply_raw(
                200,
                json.dumps(
                    {
                        "capacity": critpath.slow.capacity,
                        "budget_ms": critpath.budget_ms(),
                        "records": critpath.slow.records(),
                    },
                    default=str,
                ).encode(),
                "application/json",
            )
        else:
            self._reply(404, {"error": "not found"})

    def do_POST(self) -> None:  # noqa: N802 (stdlib API)
        # the standalone metrics server accepts only the debug POSTs; the
        # Engine API handler overrides do_POST and routes /debug/* here
        self._do_debug_post()

    def _do_debug_post(self) -> None:
        """POST /debug/profile?seconds=T — on-demand profiler capture
        (obs/profiler.py): single-flight (503 on overlap), hard-capped
        window, artifacts on disk before the 200 lands."""
        # drain any request body FIRST: these are keep-alive (HTTP/1.1)
        # connections, and unread body bytes would desync the next
        # request on the same socket into a garbage request line
        length = int(self.headers.get("Content-Length", 0) or 0)
        if length:
            try:
                self.rfile.read(length)
            except TimeoutError:
                metrics.count("engine_api.client_disconnects")
                self.close_connection = True
                return
        path, _, query = self.path.partition("?")
        if path != "/debug/profile":
            self._reply(404, {"error": "not found"})
            return
        params = dict(
            p.split("=", 1) for p in query.split("&") if "=" in p
        )
        try:
            seconds = float(params.get("seconds", "5"))
        except ValueError:
            seconds = float("nan")
        try:
            out = profiler.capture(seconds)
        except ValueError as e:
            self._reply(400, {"error": str(e)})
        except profiler.ProfileBusy as e:
            # one trace per process: overlap is operator error, shed it
            self._reply(503, {"error": str(e)})
        except profiler.ProfileError as e:
            self._reply(500, {"error": str(e)})
        else:
            self._reply(200, out)

    def _reply(self, status: int, payload: dict) -> None:
        self._reply_raw(status, json.dumps(payload).encode(), "application/json")

    def _reply_raw(self, status: int, raw: bytes, content_type: str) -> None:
        # a client that hangs up mid-response (CL restart, curl ^C) raises
        # here and would otherwise kill the handler thread silently — count
        # it and keep serving
        try:
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(raw)))
            tid = current_trace_id()
            if tid is not None:
                # the request's identity, joinable against span records,
                # flight events, and the batch that served it
                self.send_header("X-Phant-Trace", tid)
            self.end_headers()
            self.wfile.write(raw)
        except (BrokenPipeError, ConnectionResetError, TimeoutError) as e:
            # TimeoutError: a client that stopped READING (full TCP buffer)
            # is the write-side slow-loris; the socket deadline frees the
            # thread and the disconnect counter covers both directions
            metrics.count("engine_api.client_disconnects")
            log.debug("client disconnected mid-reply: %r", e)
            # stop the keep-alive loop: reading the dead socket again would
            # raise out of handle_one_request and traceback to stderr
            self.close_connection = True

    def log_message(self, fmt, *args):  # route to logging, not stderr
        log.debug(fmt, *args)


class EngineAPIServer:
    """HTTP server bound to a Blockchain (reference: main.zig:143-149).

    Owns a `VerificationScheduler` (phant_tpu/serving/): construction
    installs it as the process's active scheduler (so
    stateless.admit_witness and `/healthz` see it) and shutdown
    drains + uninstalls it. Pass `scheduler=` to share one across
    servers — then the CALLER owns its lifecycle (shutdown here only
    undoes this server's install, never drains a shared scheduler out
    from under its other users) — or `sched_config=` to size the
    queue/batch policy (the `--sched-*` CLI flags,
    phant_tpu/__main__.py)."""

    def __init__(
        self,
        blockchain,
        host: str = "127.0.0.1",
        port: int = 8551,
        scheduler: VerificationScheduler = None,
        sched_config: SchedulerConfig = None,
    ):
        self.blockchain = blockchain
        # re-resolve the obs layers' memoized configs NOW: the CLI writes
        # --slo-budget-ms / --profile-dir / --timeline-* / --flight-ring
        # into the env before constructing the server, and tests
        # monkeypatch the same keys (obs/critpath.py documents why the
        # config is not re-read per request/event)
        critpath.refresh_from_env()
        timeline.refresh_from_env()
        _refresh_flight_ring()
        self._owns_scheduler = scheduler is None
        if scheduler is None:
            scheduler = VerificationScheduler(config=sched_config)
        self.scheduler = scheduler
        # graceful-degradation valve for stateless execution (env-sized at
        # construction: PHANT_HTTP_MAX_CONCURRENT / PHANT_HTTP_GATE_PATIENCE_S)
        self._gate = _default_gate()
        outer = self

        class Handler(_ObservableHandler):
            def do_POST(self) -> None:  # noqa: N802 (stdlib API)
                if self.path.split("?", 1)[0].startswith("/debug/"):
                    # debug surface (profiler capture): not a JSON-RPC
                    # request — skip the Engine API accounting so the
                    # front-door latency histogram measures only traffic
                    return self._do_debug_post()
                # Lock-discipline audit (phantlint LOCK, PR 2): the
                # counter / in-flight gauge / latency-histogram updates
                # here deliberately run on the handler thread with no
                # exclusion — the registry has its own internal lock
                # (trace.Metrics._lock), and serializing observability
                # writes would serialize the very concurrency the
                # in-flight gauge measures. phantlint's LOCK rule scopes
                # to the lock-owning object's own attributes, so it
                # (correctly) reports nothing here — this comment, not a
                # disable annotation, is the audit record.
                #
                # one trace context per request: the trace_id rides
                # every span this thread opens and every scheduler job
                # it submits, and comes back in X-Phant-Trace. The
                # `request` frame span is the measured interval of the
                # whole POST (headers parsed -> reply written): the
                # front end's phases are its marks, verify_block its
                # child by parent_id, and its duration IS
                # engine_api.request_seconds.
                req = None
                try:
                    with trace_context(), span("request", frame=True) as req:
                        req.resume = "reply"  # what follows a verify_block
                        req.mark("read")
                        metrics.gauge_add("engine_api.inflight", 1)
                        try:
                            # The tenant context (QoS lane + priority class,
                            # serving/qos.py) rides the same thread-local
                            # channel: X-Phant-Tenant names the admission lane
                            # (sanitized — the header is attacker-controlled)
                            # and X-Phant-Priority: head marks head-of-chain
                            # work (state-mutating methods are always head
                            # class via the serial lane, so the header only
                            # matters for executeStateless).
                            tenant = sanitize_tenant(
                                self.headers.get("X-Phant-Tenant")
                            )
                            priority = (
                                PRIORITY_HEAD
                                if self.headers.get("X-Phant-Priority", "").lower()
                                == "head"
                                else PRIORITY_BACKFILL
                            )
                            with tenant_context(tenant, priority):
                                self._handle_post()
                        finally:
                            metrics.gauge_add("engine_api.inflight", -1)
                finally:
                    if req is not None:
                        walls = dict.fromkeys(FRONTEND_PHASES, 0)
                        for name, t_a, t_b in req.intervals:
                            if name in walls:
                                walls[name] += t_b - t_a
                                metrics.observe_hist(
                                    "engine_api.phase_seconds",
                                    (t_b - t_a) / 1e9,
                                    buckets=REQUEST_SECONDS_BUCKETS,
                                    phase=name,
                                )
                        # the handler's CPU inside each mark (the thread's
                        # CPU clock, read where the marks are set) and the
                        # rest of the mark's wall: what it waited there
                        for name, cpu in req.cpu_ns.items():
                            if name in walls:
                                metrics.observe_split(
                                    "engine_api.phase_cpu_seconds",
                                    "engine_api.phase_offcpu_seconds",
                                    walls[name] / 1e9,
                                    cpu / 1e9,
                                    buckets=REQUEST_SECONDS_BUCKETS,
                                    phase=name,
                                )
                        # the front-door latency histogram rides THE shared
                        # bucket table (trace.REQUEST_SECONDS_BUCKETS): buckets
                        # freeze at first observation, so a second call site
                        # with its own tuple would silently split the family —
                        # and the derived p50/p99 gauges (prometheus_text)
                        # need the overload tail the shared table carries
                        metrics.observe_hist(
                            "engine_api.request_seconds",
                            req.duration_s,
                            buckets=REQUEST_SECONDS_BUCKETS,
                        )

            def _handle_post(self) -> None:
                length = int(self.headers.get("Content-Length", 0))
                try:
                    body = self.rfile.read(length)
                except TimeoutError:
                    # slow-loris: headers arrived, the promised body never
                    # did — the socket deadline freed this thread. Count
                    # it with the other client disconnects and drop the
                    # connection (a reply would race the dead read state).
                    metrics.count("engine_api.client_disconnects")
                    log.debug("client stalled mid-body; connection dropped")
                    self.close_connection = True
                    return
                # what `read` read and `json` and `decode` are about to parse
                metrics.count("engine_api.request_body_bytes", len(body))
                req = current_span()
                req.mark("json")
                try:
                    request = json.loads(body)
                except json.JSONDecodeError:
                    metrics.count("engine_api.request_errors")
                    self._reply(400, {"error": {"code": -32700, "message": "parse error"}})
                    return
                if not isinstance(request, dict):
                    # batch requests and non-object bodies are not supported
                    metrics.count("engine_api.request_errors")
                    self._reply(
                        400,
                        {
                            "jsonrpc": "2.0",
                            "id": None,
                            "error": {"code": -32600, "message": "invalid request"},
                        },
                    )
                    return
                method = request.get("method", "")
                # `decode` ends where verify_block opens (utils/trace.span,
                # under a frame): the handler's own time is not the
                # front end's
                try:
                    if isinstance(method, str) and method.startswith(
                        _SERIAL_METHOD_PREFIXES
                    ):
                        # nothing is marked while this thread waits on the
                        # serial lane: the executor does the work
                        req.mark(None)
                        # state-mutating: exclusive execution on the
                        # scheduler's single executor thread (the global
                        # lock's replacement — admission-ordered, drained
                        # on shutdown, fails fast on executor death)
                        status, response = outer.scheduler.submit_serial(
                            lambda: handle_request(outer.blockchain, request)
                        ).result()
                    elif isinstance(method, str) and method.startswith(
                        "engine_executeStateless"
                    ):
                        # concurrently on THIS handler thread, but behind
                        # the bounded-concurrency gate: under overload the
                        # box must shed backfill fast (head-of-chain gets
                        # 8x the patience) instead of thrashing hundreds
                        # of half-done EVM re-executions
                        req.mark("gate")
                        tenant = current_tenant()
                        if not outer._gate.acquire(
                            current_priority() == PRIORITY_HEAD
                        ):
                            metrics.count(
                                "sched.rejected",
                                reason="saturated",
                                tenant=tenant,
                            )
                            flight.record(
                                "sched.shed",
                                reason="saturated",
                                lane="stateless",
                                tenant=tenant,
                            )
                            metrics.count("engine_api.request_errors")
                            self._reply(
                                503,
                                {
                                    "jsonrpc": "2.0",
                                    "id": request.get("id"),
                                    "error": {
                                        "code": -32050,
                                        "message": "node saturated: "
                                        "stateless execution shed",
                                    },
                                },
                            )
                            return
                        req.mark("decode")
                        try:
                            status, response = handle_request(
                                outer.blockchain, request
                            )
                        finally:
                            outer._gate.release()
                    else:
                        # read-only: run concurrently on THIS handler
                        # thread; any witness verification inside
                        # coalesces via the scheduler's batch assembler
                        req.mark("decode")
                        status, response = handle_request(
                            outer.blockchain, request
                        )
                except SchedulerError as e:
                    req.mark("reply")
                    # overload / deadline / executor-down: distinct
                    # JSON-RPC codes (-32050/-32051/-32052) over HTTP 503
                    metrics.count("engine_api.request_errors")
                    self._reply(
                        e.http_status,
                        {
                            "jsonrpc": "2.0",
                            "id": request.get("id"),
                            "error": {"code": e.code, "message": str(e)},
                        },
                    )
                    return
                req.mark("reply")
                if status >= 400 or "error" in response:
                    metrics.count("engine_api.request_errors")
                self._reply(status, response)

        try:
            self._server = _HTTPServer((host, port), Handler)
        except BaseException:
            # a bind failure must not leak the executor thread this
            # constructor just spawned (nobody else holds a reference)
            if self._owns_scheduler:
                scheduler.shutdown(drain=False)
            raise
        # install only after the socket bound: a bind failure must not
        # leak a process-globally installed scheduler
        install(scheduler)
        # the collector while a server is up: one callback for the process
        # times its pauses (runtime.gc_pause_seconds, `gc` intervals) and
        # tenures what survives a full collection (serving/collector.py)
        collector.install()
        try:
            _boot_root_lane()
            boot_lanes(scheduler)
        except BaseException:
            # a build that fails leaves nothing of this server installed
            try:
                if self._owns_scheduler:
                    scheduler.shutdown(drain=False)
            finally:
                uninstall(scheduler)
                self._server.server_close()
                collector.uninstall()
            raise

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def serve_forever(self) -> None:
        log.info("Engine API listening on :%d", self.port)
        self._server.serve_forever()

    def serve_in_background(self) -> threading.Thread:
        t = threading.Thread(target=self._server.serve_forever, daemon=True)
        t.start()
        return t

    def shutdown(self) -> None:
        """Graceful: stop accepting connections, then drain the scheduler
        (queued serial/witness jobs complete so in-flight handlers get
        real answers), then release the socket and the scheduler slot.
        A caller-provided (shared) scheduler is NOT drained — only this
        server's install is undone; its lifecycle belongs to the caller."""
        self._server.shutdown()
        try:
            if self._owns_scheduler:
                self.scheduler.shutdown(drain=True)
        finally:
            uninstall(self.scheduler)
            self._server.server_close()
            collector.uninstall()


def _boot_root_lane() -> None:
    """`root.plan_shapes` is on /metrics from the start; and where this
    server sends post roots to an accelerator (the device root lane on,
    stateless._batched_root_wanted), every rung of the root program's
    ladder is built now, before a request can wait for one
    (ops/root_engine.prewarm_ladder). On the CPU, where tests and dry runs
    force the lane, a rung is built in a second or two when first met."""
    from phant_tpu.ops.root_engine import note_plan_shape, prewarm_ladder
    from phant_tpu.stateless import _batched_root_wanted

    note_plan_shape()
    if not _batched_root_wanted() or on_cpu():
        return
    log.info("root lane: %d rungs built in %.1fs", *prewarm_ladder())




class MetricsServer:
    """Standalone `/metrics` + `/healthz` server (`--metrics-port`): the
    Engine API port is a localhost CL-trust interface, while scrapers may
    live elsewhere — a separate bind keeps the two audiences separable."""

    def __init__(self, host: str = "127.0.0.1", port: int = 9465):
        self._server = _HTTPServer((host, port), _ObservableHandler)

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def serve_in_background(self) -> threading.Thread:
        t = threading.Thread(target=self._server.serve_forever, daemon=True)
        t.start()
        return t

    def shutdown(self) -> None:
        self._server.shutdown()
        self._server.server_close()


def serve_metrics(host: str = "127.0.0.1", port: int = 9465) -> MetricsServer:
    """Start the standalone metrics server in a daemon thread."""
    srv = MetricsServer(host, port)
    srv.serve_in_background()
    log.info("metrics listening on %s:%d", host, srv.port)
    return srv
