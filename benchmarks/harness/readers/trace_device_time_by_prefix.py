"""Device time, in the traced stretch, of the programs whose XLA module
name (`jit_<function>`, less the fingerprint) starts with one of `prefixes`,
over the requests in flight in the stretch. Nothing where no such program ran."""

from . import traced


def read(obs, prefixes: list, scale: float = 1.0):
    t = traced(obs)
    if t is None:
        return None
    hit = [s for name, s in t["device_s_by_module"].items() if name.startswith(tuple(prefixes))]
    if not hit:
        return None
    return sum(hit) / t["requests"] * scale
