"""A number the driver put into what the run observed, under `key`: one
that is of the harness's own clock and of no family the program exports.
Nothing where the driver gave none."""


def read(obs, key: str, scale: float = 1.0):
    value = obs.get(key)
    return None if value is None else value * scale
