"""Model FLOPs of the window's forwards (the configuration's reference
module's forward_flops) per second of the window, in percent of the
chip's bf16 peak."""


def read(obs):
    if obs.get("job") != "prefill":
        return None
    rate = obs["step_flops"] * obs["steps"] / obs["window_s"]
    return 100.0 * rate / obs["peak"]["bf16_flops"]
