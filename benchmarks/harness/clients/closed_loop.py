"""Closed loop: each client thread keeps one HTTP/1.1 connection, POSTs the
next body of its list when the last is answered (after `think_ms`), and stops
at the deadline, at the end of its list, or when any client has run out of
list (the chain is never walked twice)."""

from __future__ import annotations

import http.client
import threading
import time


def _client(host, port, bodies, plan, deadline, think_s, stop, out, who):
    conn = http.client.HTTPConnection(host, port, timeout=1200)
    try:
        for idx in plan:
            if stop.is_set() or (deadline is not None and time.monotonic() >= deadline):
                return
            t0 = time.monotonic()
            try:
                conn.request(
                    "POST", "/", body=bodies[idx],
                    headers={"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                reply, code = resp.read(), resp.status
            except (OSError, http.client.HTTPException) as e:
                reply, code = repr(e).encode(), -1
                conn.close()
            out.append((who, idx, t0, time.monotonic(), code, reply))
            if think_s:
                time.sleep(think_s)
        if deadline is not None:
            stop.set()  # out of chain before the deadline: the window ends here
    finally:
        conn.close()


def run(host, port, bodies, plans, seconds, traffic) -> tuple:
    """One phase: (t_open, t_close, exhausted, records), a record being
    (client, body index, sent, answered, http code, reply bytes)."""
    stop, out = threading.Event(), []
    t_open = time.monotonic()
    deadline = None if seconds is None else t_open + seconds
    threads = [
        threading.Thread(
            target=_client,
            args=(host, port, bodies, plan, deadline, traffic["think_ms"] / 1e3, stop, out, i),
        )
        for i, plan in enumerate(plans)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    exhausted = stop.is_set()
    t_close = deadline
    if deadline is not None and exhausted:
        t_close = min(deadline, max(r[3] for r in out))
    return t_open, t_close, exhausted, out
