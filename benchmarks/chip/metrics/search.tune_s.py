"""Host time of the tuning call (benchmark span around TaskScheduler.tune)."""


def read(obs):
    tune = obs.get("tune")
    return tune["tune_s"] if tune else None
