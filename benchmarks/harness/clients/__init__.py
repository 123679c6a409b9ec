"""The clients: a process of its own that never imports jax, so that its
threads do not take the server's interpreter lock. How they send is a mode,
one module each (`clients/<mode>.py` with a `run`), named by the traffic
file's `mode` key; `closed_loop` is the first."""

from __future__ import annotations

import importlib


def serve(pipe) -> None:
    """The child's main: commands over `pipe` until "quit".

    ("bodies", {idx: bytes})  adds request bodies
    ("run", host, port, plans, seconds or None, traffic)  runs one phase in
        the traffic's mode: replies with (t_open, t_close, exhausted, records)
    """
    bodies = {}
    while True:
        msg = pipe.recv()
        if msg[0] == "quit":
            return
        if msg[0] == "bodies":
            bodies.update(msg[1])
            pipe.send("ok")
            continue
        _cmd, host, port, plans, seconds, traffic = msg
        mode = importlib.import_module(f"{__name__}.{traffic['mode']}")
        pipe.send(mode.run(host, port, bodies, plans, seconds, traffic))
