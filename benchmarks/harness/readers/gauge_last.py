"""A gauge as the program shows it at the window's close (the summed series
matching `where`). A family the program does not export reads as nothing."""

from .. import scrape


def read(obs, family: str, where: dict | None = None, scale: float = 1.0):
    if not any(name == family for name, _labels in obs["scrape1"]):
        return None
    return scrape.total(obs["scrape1"], family, where) * scale
