"""The window's length over the scheduler ticks run in it."""


def read(obs):
    if obs.get("job") != "serve" or not obs.get("ticks"):
        return None
    return 1e3 * obs["window_s"] / obs["ticks"]
