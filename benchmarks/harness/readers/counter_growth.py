"""The growth of one counter family (the series matching `where`) between
the window's two scrapes. A family the program does not export reads as
nothing: a program from before the counter is not a program that counted 0."""

from .. import scrape


def read(obs, family: str, where: dict | None = None, scale: float = 1.0):
    if not any(name == family for name, _labels in obs["scrape1"]):
        return None
    return scrape.delta(obs["scrape0"], obs["scrape1"], family, where) * scale
