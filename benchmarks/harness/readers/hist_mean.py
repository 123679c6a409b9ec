"""The growth of a histogram's `_sum` over the growth of its own `_count`."""

from .. import scrape


def read(obs, family: str, where: dict | None = None, scale: float = 1.0):
    n = scrape.delta(obs["scrape0"], obs["scrape1"], family + "_count", where)
    if n <= 0:
        return None
    return scrape.delta(obs["scrape0"], obs["scrape1"], family + "_sum", where) / n * scale
