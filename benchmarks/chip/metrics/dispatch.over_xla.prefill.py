"""Tuned forward step time over the XLA-only forward's, host clock around
block_until_ready (median of 3 steps each, before the window)."""


def read(obs):
    if obs.get("job") != "prefill":
        return None
    return obs.get("over_xla")
