"""Device time of all program executions in the traced stretch over the
requests in flight in it."""

from . import traced


def read(obs, scale: float = 1.0):
    t = traced(obs)
    if t is None:
        return None
    return sum(t["device_s_by_program"].values()) / t["requests"] * scale
