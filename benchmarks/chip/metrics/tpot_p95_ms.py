"""95th percentile over requests with two or more tokens in the window of
(last token time - first token time) / (tokens - 1)."""

import harness


def read(obs):
    if obs.get("job") != "serve":
        return None
    per = [r["tpot_s"] for r in obs["requests"] if r["tpot_s"] is not None]
    return 1e3 * harness.p95(per) if per else None
