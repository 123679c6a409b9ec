"""Seconds the collector held the server's process, per request sent."""


def read(obs, scale: float = 1.0):
    if not obs["latency_s"] or not obs["gc_pauses"]:
        return None
    return sum(p[1] for p in obs["gc_pauses"]) / len(obs["latency_s"]) * scale
