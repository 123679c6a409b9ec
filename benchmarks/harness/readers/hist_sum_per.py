"""The growth of a histogram family's `_sum` (the series matching `where`)
over the growth of a count of requests: a mean per request."""

from .. import scrape


def read(obs, family: str, where: dict, per: dict, scale: float = 1.0):
    n = scrape.delta(obs["scrape0"], obs["scrape1"], per["family"], per.get("where"))
    if n <= 0:
        return None
    grown = scrape.delta(obs["scrape0"], obs["scrape1"], family + "_sum", where)
    return grown / n * scale
