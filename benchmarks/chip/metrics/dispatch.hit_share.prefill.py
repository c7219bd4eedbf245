"""Dispatch lookups of the prefill program served by a tuned kernel, in
percent of all lookups (hits, misses and fallbacks, stats_by_key())."""

import harness


def read(obs):
    return harness.for_job(obs, "prefill", harness.hit_share)
