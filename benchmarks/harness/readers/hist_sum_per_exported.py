"""`hist_sum_per`, for a family that only a later program exports: where
the scrape at the window's close has no series of the family (the parent of
the PR that brought it), nothing, and not a mean of 0."""

from . import hist_sum_per


def read(obs, family: str, where: dict, per: dict, scale: float = 1.0):
    if not any(name == family + "_sum" for name, _labels in obs["scrape1"]):
        return None
    return hist_sum_per.read(obs, family, where, per, scale)
