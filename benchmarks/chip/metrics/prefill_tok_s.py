"""Tokens of every forward step in the window over the window's length."""


def read(obs):
    if obs.get("job") != "prefill":
        return None
    return obs["tokens"] / obs["window_s"]
