"""95th percentile over every request due in the window of the time from
when it was due to its admission into a slot (still queued at the
window's end: its wait so far)."""

import harness


def read(obs):
    if obs.get("job") != "serve":
        return None
    return 1e3 * harness.p95([r["queue_s"] for r in obs["requests"]])
