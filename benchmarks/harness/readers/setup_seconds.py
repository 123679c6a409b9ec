"""Process start to the window's opening."""


def read(obs):
    return obs["setup_s"]
