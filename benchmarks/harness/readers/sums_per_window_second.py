"""The summed growth, between the window's two scrapes, of several series
(`plus`, less `minus`: histogram `_sum` series, gauges, counters; each a
`family` with an optional `where`), over the window's seconds: a share of
one core where the series are CPU seconds. With `per`, over the growth of
that count instead: a mean a request across families. Nothing where scrape1
lacks any of the families (the parent of the PR that brought them), and
nothing where nothing grew: it never returns 0 for a share."""

from .. import scrape


def read(obs, plus: list, minus: list = (), per: dict | None = None, scale: float = 1.0):
    exported = {name for name, _labels in obs["scrape1"]}
    if any(term["family"] not in exported for term in (*plus, *minus)):
        return None

    def grown(term):
        return scrape.delta(obs["scrape0"], obs["scrape1"], term["family"], term.get("where"))

    over = grown(per) if per is not None else obs["window_s"]
    total = sum(map(grown, plus)) - sum(map(grown, minus))
    if not over or over <= 0 or total <= 0:
        return None
    return total / over * scale
