"""Share of the traced window in which no op ran on the device, in
percent (one minus the union of device op intervals over the window)."""

import harness


def read(obs):
    return harness.for_job(obs, "prefill", harness.idle_share)
