"""In step: the clients of a group stand for the consensus clients of one
operator, which receive a slot's block from gossip within the same tens of
milliseconds and post it at once. Each client thread keeps its own HTTP/1.1
connection; all clients of a group POST the i-th body of their lists when a
barrier releases them, and the barrier for i + 1 releases when every one of
them has its answer to i (after `think_ms`). The barrier is over the plans
a phase is given: a warm-up pass gives every client one, the probes one
client alone. Clients whose plans are equal form a group (the driver hands a
group's clients the same range of the chain). Deadline, end of chain and
records as `closed_loop`: a phase stops at the deadline, at the end of the
lists, or when any group has run out of list (the chain is never walked
twice), and the decision is taken between steps, by one client for its
whole group, so that no client waits at a barrier the others have left.

A client that is answered first stands idle at the barrier until the last
is, and a server may close a keep-alive connection that idles (this one
does after 30 s, which a wave that waits for a compile during warm-up can
pass). A POST that fails on a connection that has carried a request before
is therefore sent once more on a new one, as HTTP clients do (RFC 7230,
6.3.1), inside the same record: its latency counts both. A failure on a
new connection is a record with code -1, as `closed_loop`'s.

Where the traffic names a file under `releases`, the monotonic time of
every barrier's release is appended to it, a line each, as it happens: the
driver of a traced run places its stretch by them (the clock is the
machine's, the same in the driver's process)."""

from __future__ import annotations

import http.client
import threading
import time


class _Group:
    """One barrier and, decided by the last client to arrive, whether the
    group takes another step."""

    def __init__(self, size: int, deadline, stop: threading.Event, releases=None):
        self.go = True
        self._deadline, self._stop, self._releases = deadline, stop, releases
        self.barrier = threading.Barrier(size, action=self._decide)

    def _decide(self) -> None:
        now = time.monotonic()
        self.go = not ((self._deadline is not None and now >= self._deadline) or self._stop.is_set())
        if self.go and self._releases is not None:
            self._releases.write(f"{now!r}\n")
            self._releases.flush()


def _post(conn, body) -> tuple:
    conn.request("POST", "/", body=body, headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.read(), resp.status


def _client(host, port, bodies, plan, group, deadline, think_s, stop, out, who):
    conn = http.client.HTTPConnection(host, port, timeout=1200)
    used = False  # has this connection carried a request
    try:
        for idx in plan:
            group.barrier.wait()
            if not group.go:
                return
            t0 = time.monotonic()
            try:
                try:
                    reply, code = _post(conn, bodies[idx])
                except ConnectionError:
                    if not used:
                        raise
                    conn.close()  # dropped while idle at the barrier: once more, anew
                    reply, code = _post(conn, bodies[idx])
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
    """One phase: (t_open, t_close, exhausted, records), a record being
    (client, body index, sent, answered, http code, reply bytes)."""
    stop, out = threading.Event(), []
    t_open = time.monotonic()
    deadline = None if seconds is None else t_open + seconds
    sizes: dict = {}
    for plan in plans:
        sizes[tuple(plan)] = sizes.get(tuple(plan), 0) + 1
    releases = open(traffic["releases"], "a") if traffic.get("releases") else None
    groups = {key: _Group(n, deadline, stop, releases) for key, n in sizes.items()}
    threads = [
        threading.Thread(
            target=_client,
            args=(
                host, port, bodies, plan, groups[tuple(plan)], deadline,
                traffic["think_ms"] / 1e3, stop, out, i,
            ),
        )
        for i, plan in enumerate(plans)
    ]
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
