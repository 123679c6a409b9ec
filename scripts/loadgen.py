#!/usr/bin/env python
"""loadgen: open-loop traffic generator for the verification serving stack.

The QoS layer (per-tenant lanes, priority preemption, adaptive batching,
overload shedding — phant_tpu/serving/) claims to keep head-of-chain
latency bounded and every tenant progressing while the scheduler is
saturated. Nothing in the tree could PRODUCE that saturation: the soak is
closed-loop (each thread waits for its reply, so offered load politely
collapses to service rate — the classic coordinated-omission trap).
This harness closes the gap: an
OPEN-LOOP generator (arrivals fire on a Poisson clock regardless of how
slow replies are, so queueing delay is measured, not hidden) that drives
the REAL HTTP server with a mixed-tenant profile and reports what the QoS
machinery actually did.

Traffic model:

* **Poisson arrivals** at each offered rate, with periodic BURSTS (the
  rate multiplies by `burst_factor` for `burst_len_s` out of every
  `burst_period_s`) — steady-state averages hide exactly the transient
  the per-tenant quotas exist for;
* **mixed tenant profile** — by default `backfill` (a replaying indexer:
  `engine_executeStatelessPayloadV1`, backfill class) and `head` (a
  consensus client: `engine_newPayloadV2` on the serial lane +
  priority-header stateless checks) at 10:1 offered load;
* **`--profile mixed`** — the backfill tenant draws from a
  witness-size-DIVERSE body set (build_mixed_bodies): a hot head shape
  carrying most of the load, a same-bucket twin with different node
  bytes, and a tail of progressively larger witnesses, weighted with
  mainnet-shaped reuse skew (PAPERS.md 2408.14217) — so per-bucket
  assembly, the mesh router (`--sched-mesh`), and per-device intern
  tables are exercised under the tenant mix;
* **slow-loris clients** — raw sockets that send headers, promise a body,
  and stall; the server's socket deadline (PHANT_HTTP_TIMEOUT_S) must
  free the pinned handler threads and count the disconnects;
* a **saturation sweep**: the same profile at >= 3 offered-load points
  (default 0.5x / 1x / 2x of a quick closed-loop capacity estimate), so
  throughput-vs-offered-load draws the knee instead of a single point.

Per point it reports achieved arrival rate, goodput, shed rate (by
JSON-RPC code -32050/-32051/-32052), p50/p99/p999 latency, per-tenant
goodput, and head-class p99; the run-level verdicts — zero serial-lane
sheds, nonzero adaptive-wait adjustments, and NO TENANT STARVED during
the overload point — come from the server's own flight recorder
(`/debug/flight`, PR 4) and `/metrics`, not from client-side bookkeeping.

Faces: `python scripts/loadgen.py` (self-serves an EngineAPIServer on an
ephemeral port; `--base URL` aims at an external server instead), and
`make soak` runs a <=60s fixed-seed phase (scripts/soak.py).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_SHED_CODES = (-32050, -32051, -32052)


# ---------------------------------------------------------------------------
# request plumbing
# ---------------------------------------------------------------------------


_conn_tls = threading.local()

#: reuse window: a kept-alive connection idle longer than this is re-dialed
#: BEFORE sending (the server's own idle deadline, PHANT_HTTP_TIMEOUT_S,
#: would have closed it — paying a failed send + retry per request doubles
#: measured latency for nothing). run_profile() sets it under the server
#: deadline it arms.
_IDLE_REUSE_S = [20.0]


def _post(base: str, body: bytes, headers: dict, timeout: float = 60.0):
    """(status, parsed_json) over a PERSISTENT per-thread HTTP/1.1
    connection; transport errors raise (counted by the caller as `error`).

    Keep-alive is load-bearing, not an optimization: with one fresh TCP
    connection per request, the server's single accept loop is one thread
    among hundreds of CPU-busy handlers and GIL starvation turns IT into
    the bottleneck queue — measured at ~6 concurrent requests in do_POST
    under a 160-thread hammer, so overload piled up invisibly in front of
    all the admission control this harness exists to exercise. Real CL /
    indexer clients hold persistent connections; so does loadgen. A
    server-closed (idle-deadline) connection is re-dialed once."""
    import http.client

    host, _, port = base.split("//", 1)[1].partition(":")
    key = f"conn_{host}_{port}"
    now = time.monotonic()
    for attempt in (0, 1):
        entry = getattr(_conn_tls, key, None)
        if entry is not None and now - entry[1] > _IDLE_REUSE_S[0]:
            entry[0].close()
            entry = None
        if entry is None:
            entry = [
                http.client.HTTPConnection(host, int(port), timeout=timeout),
                now,
            ]
            setattr(_conn_tls, key, entry)
        conn = entry[0]
        try:
            conn.request(
                "POST",
                "/",
                body=body,
                headers={"Content-Type": "application/json", **headers},
            )
            resp = conn.getresponse()
            data = resp.read()
            entry[1] = time.monotonic()
            return resp.status, json.loads(data)
        except Exception:
            # stale keep-alive (server idle-closed it) or a real failure:
            # re-dial once, then let the error surface
            conn.close()
            setattr(_conn_tls, key, None)
            if attempt:
                raise
    raise RuntimeError("unreachable")


def _get_json(base: str, path: str, timeout: float = 30.0):
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return json.loads(e.read())


def _get_text(base: str, path: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(base + path, timeout=timeout) as resp:
        return resp.read().decode()


def _metric_total(metrics_text: str, family: str) -> float:
    """Sum every series of a Prometheus family in a /metrics scrape."""
    total = 0.0
    for line in metrics_text.splitlines():
        if line.startswith(family) and not line.startswith("#"):
            name = line.split(" ", 1)[0]
            if name == family or name.startswith(family + "{"):
                try:
                    total += float(line.rsplit(" ", 1)[1])
                except ValueError:
                    pass
    return total


class TenantProfile:
    """One traffic class: a tenant tag, the request it sends, its share of
    the offered load, and its priority header."""

    def __init__(self, name: str, kind: str, share: float, head: bool = False):
        self.name = name
        self.kind = kind  # "stateless" | "newpayload"
        self.share = float(share)
        self.head = head

    def headers(self) -> dict:
        h = {"X-Phant-Tenant": self.name}
        if self.head:
            h["X-Phant-Priority"] = "head"
        return h


def default_profiles() -> list:
    """The 10:1 backfill:head mix the fairness acceptance tests pin — a
    replaying indexer next to a consensus client."""
    return [
        TenantProfile("backfill", "stateless", share=10.0),
        TenantProfile("head", "newpayload", share=1.0, head=True),
    ]


#: `--profile mixed`: witness-size-diverse stateless bodies with
#: mainnet-shaped REUSE SKEW (PAPERS.md 2408.14217: trie-node reuse across
#: blocks is heavy and head-skewed). Each spec is (extra_accounts,
#: witness_accounts, salt, weight): a hot head shape carries most of the
#: offered load (the steady-state chain-head witness every CL re-checks),
#: a warm twin shares its shape BUCKET but not its node bytes, and a tail
#: of progressively larger witnesses (deeper tries, more proofs -> other
#: pow2 buckets) exercises per-bucket assembly, the mesh router's
#: affinity/spillover split, and per-device intern tables under tenant
#: mixes — the traffic where tenant cost skew actually bites.
_MIXED_SPECS = (
    (23, 0, 0, 0.45),    # hot head shape: heavy reuse, warm tables
    (23, 0, 1, 0.15),    # same bucket, different bytes (intern miss)
    (63, 8, 0, 0.15),    # mid-size witness
    (127, 24, 0, 0.10),
    (255, 48, 0, 0.08),  # large witness, deep proofs
    (319, 96, 1, 0.07),  # cold tail: rare, big, mostly-novel bytes
)


def build_mixed_bodies(log=lambda msg: None) -> tuple:
    """([body_bytes, ...], [cumulative_weight, ...]) for the mixed
    profile — each body an independently consensus-valid
    executeStateless request (tests/test_serving.py _stateless_request
    with the size knobs)."""
    from test_serving import _stateless_request  # noqa: E402

    bodies: list = []
    weights: list = []
    for extra, witnessed, salt, weight in _MIXED_SPECS:
        _chain, rpc, _root = _stateless_request(
            extra_accounts=extra, witness_accounts=witnessed, salt=salt
        )
        body = json.dumps(rpc).encode()
        n_nodes = len(rpc["params"][1]["state"])
        log(f"mixed body: {extra} accts, {n_nodes} witness nodes, w={weight}")
        bodies.append(body)
        weights.append(weight)
    total = sum(weights)
    cum, acc = [], 0.0
    for w in weights:
        acc += w / total
        cum.append(acc)
    return bodies, cum


def _pick_body(bodies: dict, kind: str, rng):
    """The request body for one arrival: a plain bytes entry, or a
    weighted (bodies, cum) tuple drawn per arrival (the mixed profile's
    reuse skew lives in these weights)."""
    body = bodies[kind]
    if isinstance(body, tuple):
        blist, cum = body
        u = rng.random()
        return blist[next(k for k, c in enumerate(cum) if u <= c)]
    return body


# ---------------------------------------------------------------------------
# percentiles (no numpy dependency on the hot path; samples are small)
# ---------------------------------------------------------------------------


def _percentile(sorted_vals, q: float):
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _lat_summary(lat_ms) -> dict:
    s = sorted(lat_ms)
    return {
        "n": len(s),
        "p50_ms": round(_percentile(s, 0.50), 3) if s else None,
        "p99_ms": round(_percentile(s, 0.99), 3) if s else None,
        "p999_ms": round(_percentile(s, 0.999), 3) if s else None,
    }


# ---------------------------------------------------------------------------
# open-loop point runner
# ---------------------------------------------------------------------------


class _Recorder:
    """Thread-safe per-request sample sink."""

    def __init__(self):
        self.lock = threading.Lock()
        self.samples: list = []  # (tenant, kind, outcome, latency_ms)
        self.outstanding = 0
        self.client_dropped = 0

    def add(self, tenant, kind, outcome, lat_ms):
        with self.lock:
            self.samples.append((tenant, kind, outcome, lat_ms))


def _one_request(base: str, prof: TenantProfile, body: bytes, rec: _Recorder):
    t0 = time.perf_counter()
    try:
        code, reply = _post(base, body, prof.headers())
    except Exception:
        rec.add(prof.name, prof.kind, "error", (time.perf_counter() - t0) * 1e3)
        return
    finally:
        with rec.lock:
            rec.outstanding -= 1
    lat = (time.perf_counter() - t0) * 1e3
    err = reply.get("error") if isinstance(reply, dict) else None
    if err and err.get("code") in _SHED_CODES:
        rec.add(prof.name, prof.kind, f"shed:{err['code']}", lat)
    elif code == 200 and not err:
        rec.add(prof.name, prof.kind, "ok", lat)
    else:
        rec.add(prof.name, prof.kind, "error", lat)


def run_point(
    base: str,
    profiles,
    bodies: dict,
    rate_rps: float,
    duration_s: float,
    rng,
    pool: ThreadPoolExecutor,
    burst_factor: float = 2.0,
    burst_period_s: float = 10.0,
    burst_len_s: float = 2.0,
    max_outstanding: int = 512,
) -> dict:
    """One open-loop measurement point: Poisson arrivals at `rate_rps`
    (bursting to `burst_factor`x) for `duration_s`, tenants drawn by
    share. Arrivals never wait for completions — that is the point."""
    rec = _Recorder()
    shares = [p.share for p in profiles]
    total_share = sum(shares)
    cum = []
    acc = 0.0
    for s in shares:
        acc += s / total_share
        cum.append(acc)
    t_start = time.monotonic()
    t_end = t_start + duration_s
    arrivals = 0
    now = t_start
    while now < t_end:
        in_burst = burst_factor > 1 and (now - t_start) % burst_period_s < burst_len_s
        rate = rate_rps * (burst_factor if in_burst else 1.0)
        now += rng.exponential(1.0 / rate) if rate > 0 else duration_s
        delay = now - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        if time.monotonic() >= t_end:
            break
        u = rng.random()
        prof = profiles[next(i for i, c in enumerate(cum) if u <= c)]
        with rec.lock:
            if rec.outstanding >= max_outstanding:
                # open-loop honesty: the client refuses to hide overload by
                # queueing client-side; a dropped arrival is reported, not
                # silently retried
                rec.client_dropped += 1
                continue
            rec.outstanding += 1
        arrivals += 1
        pool.submit(_one_request, base, prof, _pick_body(bodies, prof.kind, rng), rec)
    # drain: everything submitted gets to finish (sheds resolve fast; ok
    # replies are bounded by the server's own deadline)
    t_drain = time.monotonic()
    while True:
        with rec.lock:
            if rec.outstanding == 0:
                break
        if time.monotonic() - t_drain > 120:
            break
        time.sleep(0.01)
    wall = time.monotonic() - t_start
    samples = rec.samples
    ok = [s for s in samples if s[2] == "ok"]
    shed = [s for s in samples if s[2].startswith("shed")]
    errors = [s for s in samples if s[2] == "error"]
    per_tenant = {}
    for p in profiles:
        t_ok = [s for s in ok if s[0] == p.name]
        t_all = [s for s in samples if s[0] == p.name]
        per_tenant[p.name] = {
            "offered": len(t_all),
            "ok": len(t_ok),
            "tput_rps": round(len(t_ok) / wall, 2),
            "shed": len([s for s in t_all if s[2].startswith("shed")]),
            **_lat_summary([s[3] for s in t_ok]),
        }
    head_lat = [s[3] for s in ok if s[0] == "head"]
    outcomes: dict = {}
    for smp in samples:
        outcomes[smp[2]] = outcomes.get(smp[2], 0) + 1
    out = {
        "offered_rps": round(rate_rps, 2),
        "outcomes": outcomes,
        "achieved_arrival_rps": round(arrivals / wall, 2),
        "duration_s": round(wall, 1),
        "requests": len(samples),
        "tput_rps": round(len(ok) / wall, 2),
        "shed_rate": round(len(shed) / max(1, len(samples)), 4),
        "errors": len(errors),
        "client_dropped": rec.client_dropped,
        "per_tenant": per_tenant,
        **_lat_summary([s[3] for s in ok]),
    }
    if head_lat:
        out["head_p99_ms"] = _lat_summary(head_lat)["p99_ms"]
    return out


# ---------------------------------------------------------------------------
# slow-loris clients
# ---------------------------------------------------------------------------


def run_slow_loris(host: str, port: int, n: int, hold_s: float) -> dict:
    """Open `n` sockets, send headers promising a body that never comes,
    and verify the server CLOSES each within `hold_s` (it will, iff the
    socket deadline is armed — the pre-fix server pinned one handler
    thread per loris forever)."""
    closed = 0

    def loris():
        nonlocal closed
        try:
            s = socket.create_connection((host, port), timeout=10)
            s.sendall(
                b"POST / HTTP/1.1\r\nHost: loadgen\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: 4096\r\n\r\n" + b'{"stall'
            )
            s.settimeout(hold_s)
            try:
                data = s.recv(1024)
                if data == b"":
                    closed += 1  # server hung up: the deadline fired
            except socket.timeout:
                pass  # still open after hold_s: the server is pinned
            finally:
                s.close()
        except OSError:
            pass

    threads = [threading.Thread(target=loris) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(hold_s + 15)
    return {"loris_clients": n, "loris_closed_by_server": closed}


# ---------------------------------------------------------------------------
# the full profile
# ---------------------------------------------------------------------------


def _calibrate(base: str, body: bytes, headers: dict, seconds: float, conc: int) -> float:
    """Closed-loop capacity estimate: `conc` workers hammering stateless
    requests for `seconds` — only used to place the open-loop points."""
    done = [0]
    stop = time.monotonic() + seconds

    def worker():
        while time.monotonic() < stop:
            try:
                code, reply = _post(base, body, headers)
            except Exception:
                continue
            if code == 200:
                done[0] += 1

    threads = [threading.Thread(target=worker) for _ in range(conc)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    return max(5.0, done[0] / wall)


def run_profile(
    base: str = None,
    seed: int = 6,
    duration_s: float = 20.0,
    multipliers=(0.5, 1.0, 2.0),
    slow_loris: int = 2,
    loris_timeout_s: float = 2.0,
    burst_factor: float = 2.0,
    profile: str = "default",
    mesh_devices: int = 0,
    log=lambda msg: print(f"[loadgen] {msg}", file=sys.stderr),
) -> dict:
    """The whole harness: (optionally self-served) server, calibration,
    the saturation sweep, slow-loris clients during the overload point,
    and the flight-recorder no-starvation verdict. Returns the result
    dict; raises nothing on QoS violations (the `checks` sub-dict carries
    the verdicts for callers that gate — soak, tests).

    `profile="mixed"` swaps the single fixture witness for the
    witness-size-diverse body set (build_mixed_bodies: mixed shape
    buckets with mainnet-shaped reuse skew); `mesh_devices=N` serves the
    self-served sweep through `--sched-mesh N` (per-device executors +
    bucket-affinity routing) so the mesh router and per-device intern
    tables are exercised under the tenant mix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    server = None
    own_server = base is None
    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"
        ),
    )
    from test_serving import _stateless_request  # noqa: E402

    if own_server:
        # the handler reads the env per accepted connection: tighten the
        # read deadline so the loris verdict lands inside the run
        os.environ["PHANT_HTTP_TIMEOUT_S"] = str(loris_timeout_s)
        # reuse kept-alive connections only while the server would still
        # have them open (see _IDLE_REUSE_S)
        _IDLE_REUSE_S[0] = max(0.5, loris_timeout_s * 0.6)
        from phant_tpu.engine_api.server import EngineAPIServer
        from phant_tpu.serving import SchedulerConfig

        chain, stateless_rpc, _root = _stateless_request()
        server = EngineAPIServer(
            chain,
            host="127.0.0.1",
            port=0,
            sched_config=SchedulerConfig(
                max_batch=32,
                max_wait_ms=5.0,
                queue_depth=96,
                tenant_quota=64,
                deadline_ms=10_000.0,
                mesh_devices=mesh_devices,
            ),
        )
        server.serve_in_background()
        base = f"http://127.0.0.1:{server.port}"
    else:
        _chain, stateless_rpc, _root = _stateless_request()

    from test_serving import _valid_payload_json  # noqa: E402

    newpayload_rpc = {
        "jsonrpc": "2.0",
        "id": 1,
        "method": "engine_newPayloadV2",
        "params": [_valid_payload_json()],
    }
    bodies = {
        "stateless": json.dumps(stateless_rpc).encode(),
        "newpayload": json.dumps(newpayload_rpc).encode(),
    }
    if profile == "mixed":
        bodies["stateless"] = build_mixed_bodies(log)
    elif profile != "default":
        raise ValueError(f"unknown loadgen profile {profile!r}")
    profiles = default_profiles()
    result = {
        "seed": seed,
        "duration_s": duration_s,
        "base": base,
        "profile": profile,
        "mesh_devices": mesh_devices if own_server else None,
    }
    try:
        log("calibrating (closed-loop) ...")
        cap = _calibrate(
            base,
            # mixed profile: calibrate on the HOT body (the capacity that
            # places the sweep should reflect the dominant shape)
            bodies["stateless"][0][0]
            if profile == "mixed"
            else bodies["stateless"],
            {"X-Phant-Tenant": "calibrate"},
            seconds=min(4.0, duration_s / 3),
            conc=8,
        )
        result["capacity_rps_est"] = round(cap, 2)
        log(f"capacity estimate {cap:.0f} rps; sweeping {multipliers}")

        m0 = _get_text(base, "/metrics")
        adj0 = _metric_total(m0, "phant_sched_adaptive_wait_adjustments_total")
        points = []
        overload_t0 = None
        loris = {}
        with ThreadPoolExecutor(max_workers=96) as pool:
            for i, mult in enumerate(multipliers):
                rate = cap * mult
                is_overload = mult == max(multipliers)
                if is_overload:
                    overload_t0 = time.time()
                    if slow_loris:
                        loris_box = {}

                        def _loris_bg():
                            loris_box.update(
                                run_slow_loris(
                                    base.split("//")[1].split(":")[0],
                                    int(base.rsplit(":", 1)[1]),
                                    slow_loris,
                                    hold_s=loris_timeout_s * 2 + 3,
                                )
                            )

                        lt = threading.Thread(target=_loris_bg)
                        lt.start()
                log(f"point {i}: offered {rate:.0f} rps ({mult}x) for {duration_s:.0f}s")
                pt = run_point(
                    base,
                    profiles,
                    bodies,
                    rate,
                    duration_s,
                    rng,
                    pool,
                    burst_factor=burst_factor,
                )
                pt["multiplier"] = mult
                points.append(pt)
                if is_overload and slow_loris:
                    lt.join(60)
                    loris = loris_box
        result["points"] = points
        result.update(loris)

        # --- server-side verdicts (flight recorder + metrics) --------------
        m1 = _get_text(base, "/metrics")
        adj1 = _metric_total(m1, "phant_sched_adaptive_wait_adjustments_total")
        ring = _get_json(base, "/debug/flight").get("records", [])
        serial_sheds = [
            r
            for r in ring
            if r.get("kind") == "sched.shed" and r.get("lane") == "serial"
        ]
        # no-starvation: during the overload window every profiled tenant
        # must appear in completed-batch records (the flight recorder is
        # the server's own account of who actually got served)
        overload_done = [
            r
            for r in ring
            if r.get("kind") == "sched.batch_done"
            and (overload_t0 is None or r.get("t", 0) >= overload_t0)
        ]
        served_tenants = set()
        for r in overload_done:
            served_tenants.update(r.get("tenants") or [])
        starved = [
            p.name for p in profiles if p.name not in served_tenants
        ]
        result["checks"] = {
            "serial_lane_sheds": len(serial_sheds),
            "adaptive_wait_adjustments": int(adj1 - adj0),
            "tenants_served_under_overload": sorted(served_tenants),
            "starved_tenants": starved,
            "no_starvation": not starved,
            "loris_all_closed": (
                loris.get("loris_closed_by_server") == loris.get("loris_clients")
                if loris
                else None
            ),
        }
    finally:
        if server is not None:
            server.shutdown()
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--base", default=None, help="target server URL (default: self-serve)")
    p.add_argument("--seed", type=int, default=6)
    p.add_argument("--duration", type=float, default=20.0, help="seconds per load point")
    p.add_argument(
        "--multipliers",
        default="0.5,1.0,2.0",
        help="offered-load points as multiples of the capacity estimate",
    )
    p.add_argument("--slow-loris", type=int, default=2)
    p.add_argument("--loris-timeout", type=float, default=2.0,
                   help="server read deadline armed for self-serve runs")
    p.add_argument("--burst-factor", type=float, default=2.0)
    p.add_argument(
        "--profile",
        choices=("default", "mixed"),
        default="default",
        help="'mixed' drives witness-size-diverse stateless bodies with "
        "mainnet-shaped reuse skew (multiple shape buckets) instead of "
        "the single fixture witness",
    )
    p.add_argument(
        "--sched-mesh",
        type=int,
        default=0,
        metavar="N",
        help="self-served runs only: serve through a mesh executor pool "
        "of N device lanes (--sched-mesh N on the server)",
    )
    p.add_argument("--json", action="store_true", help="print the full result JSON")
    p.add_argument("--out", default=None, help="write the full result JSON here")
    args = p.parse_args(argv)

    mults = tuple(float(m) for m in args.multipliers.split(","))
    result = run_profile(
        base=args.base,
        seed=args.seed,
        duration_s=args.duration,
        multipliers=mults,
        slow_loris=args.slow_loris,
        loris_timeout_s=args.loris_timeout,
        burst_factor=args.burst_factor,
        profile=args.profile,
        mesh_devices=args.sched_mesh,
    )
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    if args.json:
        print(json.dumps(result, indent=1))
    else:
        for pt in result["points"]:
            print(
                f"[loadgen] {pt['multiplier']}x: offered {pt['offered_rps']} rps "
                f"-> tput {pt['tput_rps']} rps, shed {pt['shed_rate']:.1%}, "
                f"p50 {pt.get('p50_ms')}ms p99 {pt.get('p99_ms')}ms "
                f"p999 {pt.get('p999_ms')}ms"
            )
        print(f"[loadgen] checks: {json.dumps(result['checks'])}")
    checks = result["checks"]
    ok = (
        checks["serial_lane_sheds"] == 0
        and checks["no_starvation"]
        and checks["adaptive_wait_adjustments"] > 0
    )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
