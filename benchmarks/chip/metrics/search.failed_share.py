"""Share of measured candidates that failed to build or run, in percent."""


def read(obs):
    tune = obs.get("tune")
    if not tune or not tune["measured"]:
        return None
    return 100.0 * tune["failed"] / tune["measured"]
