"""In step, by tenant: `in_step` with one header more. The clients of a
group stand for the consensus clients of ONE operator among the several who
share a verifier, and every request of a group carries that operator's
`X-Phant-Tenant` (the traffic's `tenants`, one name a group), which the
program binds to an admission lane of its own (`phant_tpu/serving/qos.py`).
The group of a client is the index of its plan among the phase's distinct
plans, in the order the driver hands them over: a warm-up pass and the window
give every group its plan, the probes' one plan posts as the first tenant.

The groups start `stagger_ms` apart (the traffic's; 0 where it has none):
group g's clients wait g times that before their first barrier. A deployment's
operators are not in step with each other, and clients that all start at the
window's opening stay in step for as long as their rounds take the same time:
the first round is then a wave of all the clients at once, which no later
round is, it alone filled the window's slowest twentieth, and the window's
edge then cut sixteen answers at once (PERF.md section 6, PR 36).

The barrier (`in_step._Group`) and the `releases` file are `in_step`'s own.
Its `run` and its client's loop take no header, so both are written out
here with the tenant as an argument: the same records, deadline, end of
chain and second try on a connection dropped while idle."""

from __future__ import annotations

import http.client
import threading
import time

from .in_step import _Group


def _post(conn, body, tenant: str) -> tuple:
    headers = {"Content-Type": "application/json", "X-Phant-Tenant": tenant}
    conn.request("POST", "/", body=body, headers=headers)
    resp = conn.getresponse()
    return resp.read(), resp.status


def _client(host, port, bodies, plan, group, tenant, wait_s, deadline, think_s, stop, out, who):
    conn = http.client.HTTPConnection(host, port, timeout=1200)
    used = False  # has this connection carried a request
    try:
        time.sleep(wait_s)
        for idx in plan:
            group.barrier.wait()
            if not group.go:
                return
            t0 = time.monotonic()
            try:
                try:
                    reply, code = _post(conn, bodies[idx], tenant)
                except ConnectionError:
                    if not used:
                        raise
                    conn.close()  # dropped while idle at the barrier: once more, anew
                    reply, code = _post(conn, bodies[idx], tenant)
                used = True
            except (OSError, http.client.HTTPException) as e:
                reply, code = repr(e).encode(), -1
                conn.close()
                used = False
            out.append((who, idx, t0, time.monotonic(), code, reply))
            if think_s:
                time.sleep(think_s)
        if deadline is not None:
            stop.set()  # out of chain before the deadline: the window ends here
    except BaseException:
        group.barrier.abort()  # the others must not wait for a client that is gone
        raise
    finally:
        conn.close()


def run(host, port, bodies, plans, seconds, traffic) -> tuple:
    """One phase, as `in_step.run`: (t_open, t_close, exhausted, records)."""
    stop, out = threading.Event(), []
    t_open = time.monotonic()
    deadline = None if seconds is None else t_open + seconds
    keys = [tuple(plan) for plan in plans]
    distinct = list(dict.fromkeys(keys))
    releases = open(traffic["releases"], "a") if traffic.get("releases") else None
    groups = {key: _Group(keys.count(key), deadline, stop, releases) for key in distinct}
    tenants, stagger_s = traffic["tenants"], traffic.get("stagger_ms", 0) / 1e3
    threads = [
        threading.Thread(
            target=_client,
            args=(
                host, port, bodies, plan, groups[key], tenants[distinct.index(key) % len(tenants)],
                distinct.index(key) * stagger_s, deadline, traffic["think_ms"] / 1e3, stop, out, i,
            ),
        )
        for i, (plan, key) in enumerate(zip(plans, keys))
    ]  # fmt: skip
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if releases is not None:
        releases.close()
    exhausted = stop.is_set()
    t_close = deadline
    if deadline is not None and exhausted:
        t_close = min(deadline, max(r[3] for r in out))
    return t_open, t_close, exhausted, out
