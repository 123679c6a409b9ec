"""Mean client latency less the program's own mean account of the request:
what is spent outside every span the program has."""

from statistics import fmean

from . import hist_sum_per


def read(obs, family: str, where: dict, per: dict, scale: float = 1.0):
    inside = hist_sum_per.read(obs, family, where, per)
    if inside is None or not obs["latency_s"]:
        return None
    return (fmean(obs["latency_s"]) - inside) * scale
