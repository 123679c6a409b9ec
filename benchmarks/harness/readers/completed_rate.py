"""Requests answered correctly by the window's close over the window's seconds."""


def read(obs):
    if not obs["window_s"] or not obs["completed"]:
        return None
    return obs["completed"] / obs["window_s"]
