"""How far apart the values of one label lie in a histogram family: the
mean of each value's series over the window (growth of `_sum` over growth of
`_count`), then (largest - smallest) over the mean of those means. Nothing
where fewer than two values grew: a program without the family, or traffic
with one tenant, has no skew to read."""

from .. import scrape


def read(obs, family: str, label: str, scale: float = 100.0):
    values = {dict(labels).get(label) for (name, labels) in obs["scrape1"] if name == family + "_count"}
    means = []
    for value in sorted(v for v in values if v is not None):
        n = scrape.delta(obs["scrape0"], obs["scrape1"], family + "_count", {label: value})
        if n > 0:
            means.append(scrape.delta(obs["scrape0"], obs["scrape1"], family + "_sum", {label: value}) / n)
    if len(means) < 2 or sum(means) <= 0:
        return None
    return (max(means) - min(means)) / (sum(means) / len(means)) * scale
