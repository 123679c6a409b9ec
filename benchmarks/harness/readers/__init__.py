"""Reader kinds, one module each. A metric is a data file (end_to_end/ or
layer_metrics/<name>.json) whose `read` group names a kind and its
parameters; the harness finds `readers/<kind>.py` by that name and calls its
`read(obs, **parameters)` with what one run observed:

  obs["latency_s"]     client seconds of every request sent in the window
  obs["completed"]     requests answered correctly by the window's close
  obs["window_s"]      seconds between the window's edges
  obs["setup_s"]       seconds from process start to the window's opening
  obs["scrape0/1"]     the program's /metrics at the window's two edges
  obs["compiles"]      programs first built between the edges
  obs["gc_pauses"]     (start, seconds, generation) of the collector's runs in
                       the server's process between the edges
  obs["trace"]         trace_reduce's reduction of the traced stretch, with
                       the stretch's "window_s", "requests" (those in flight
                       in it, each by the share of its time inside) and "pace"
                       (they a second over the answers a second of the rest of
                       the window) and the traffic's "min_pace", or None
  obs["rehearsal"]     true on the CPU: every device reading is withheld

A reader that finds nothing to read returns None, and the harness leaves
the metric out; it never returns 0 for a share. A new kind is a new module
here, and a new metric of a kind that is here is a new data file."""

from __future__ import annotations

import importlib


def read(spec: dict, obs: dict):
    params = dict(spec["read"])
    kind = importlib.import_module(f"{__name__}.{params.pop('kind')}")
    return kind.read(obs, **params)


def traced(obs: dict):
    """The traced stretch's reduction, or None where a device reading would
    not be the cell's: on the CPU, with no trace, with no request in
    flight in the stretch, or where the profiler held the host back so far that the
    stretch ran at under `min_pace` of the rest of the window's rate."""
    t = obs.get("trace")
    if obs["rehearsal"] or not t or not t["requests"] or not t["window_s"]:
        return None
    if t["pace"] is not None and t["pace"] < t["min_pace"]:
        return None
    return t
