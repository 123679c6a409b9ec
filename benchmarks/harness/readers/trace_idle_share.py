"""1 - the union of device-operation intervals over the traced stretch."""

from . import traced


def read(obs, scale: float = 100.0):
    t = traced(obs)
    if t is None:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * scale
