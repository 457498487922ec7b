"""Seconds in which an operation ran on the device (union of the device
operations' intervals in the traced window, averaged over the chips), per
completed action of that window."""


def read(obs: dict):
    if not obs["trace"] or not obs["actions"]:
        return None
    return obs["trace"]["busy_s"] / obs["actions"]
