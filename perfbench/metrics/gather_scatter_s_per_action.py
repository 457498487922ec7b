"""Device seconds per action in gather-scatter fusions (the trace
reduction's category of that name: on the TPU a gather or a scatter is a
fusion of kind kCustom), summed over every operation of the traced window,
not the ten longest. Where the window ran none there is nothing to read."""


def read(obs: dict):
    if not obs["trace"] or not obs["actions"]:
        return None
    seconds = sum(s for _op, cat, s, _n in obs["trace"]["ops"]
                  if cat == "gather-scatter fusion")
    return seconds / obs["actions"] if seconds > 0 else None
