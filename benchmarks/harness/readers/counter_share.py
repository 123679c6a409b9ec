"""Growth of one counter over the summed growth of several."""

from .. import scrape


def read(obs, part: dict, whole: list, scale: float = 100.0):
    def grown(c):
        return scrape.delta(obs["scrape0"], obs["scrape1"], c["family"], c.get("where"))

    denom = sum(grown(c) for c in whole)
    if denom <= 0:
        return None
    return grown(part) / denom * scale
