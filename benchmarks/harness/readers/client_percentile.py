"""A percentile of the client's seconds over EVERY request sent in the window."""

from ..stats import percentile


def read(obs, q: float, scale: float = 1.0):
    if not obs["latency_s"]:
        return None
    return percentile(obs["latency_s"], q) * scale
