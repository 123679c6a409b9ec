"""Programs first built between the window's edges."""


def read(obs):
    return float(obs["compiles"])
