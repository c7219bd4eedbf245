"""95th percentile over every request due in the window of the time from
when it was due to its first token; a request with no token by the
window's end counts with its wait so far."""

import harness


def read(obs):
    if obs.get("job") != "serve":
        return None
    return 1e3 * harness.p95([r["ttft_s"] for r in obs["requests"]])
