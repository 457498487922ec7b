"""Device seconds per action in collective operations (the trace reduction's
category `collective`: all-to-all, all-reduce, all-gather, reduce-scatter,
collective-permute), self time averaged over the device planes and summed
over every operation of the traced window. Where the window ran none (one
chip: the exchange is a passthrough) there is nothing to read."""


def read(obs: dict):
    if not obs["trace"] or not obs["actions"]:
        return None
    seconds = sum(s for _op, cat, s, _n in obs["trace"]["ops"]
                  if cat == "collective")
    return seconds / obs["actions"] if seconds > 0 else None
