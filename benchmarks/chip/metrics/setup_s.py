"""Set-up: process start to the opening of the measured window (weights,
extraction, tuning, dispatch context, compiles and warm-up)."""


def read(obs):
    return obs.get("setup_s")
