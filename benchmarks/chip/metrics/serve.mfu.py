"""Sum over the traced run's window ticks of the least time each tick's
real work needs (the larger of its FLOPs over peak and its weight plus
live KV bytes over HBM bandwidth, the reference module's serve_tick),
over the window, in percent."""


def read(obs):
    if obs.get("job") != "serve" or "tick_ideal_s" not in obs:
        return None
    return 100.0 * obs["tick_ideal_s"] / obs["window_s"]
