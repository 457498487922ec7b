"""The action's share of the HBM roofline: the least time the chip could
take to move the bytes the action must move whatever implements it (the
configuration's `least_bytes`: every input column it uses read once, its
result written once) at the chip's peak bandwidth (peaks.json), over the
device's busy seconds per action. The bytes are a true lower bound, so the
share cannot pass 100%. Nothing to read gives nothing, never 0."""


def read(obs: dict):
    if not obs["trace"] or not obs["actions"] or not obs["peaks"]:
        return None
    busy = obs["trace"]["busy_s"] / obs["actions"]
    if busy <= 0:
        return None
    least_s = (obs["least_bytes_per_action"] / obs["chips"]
               / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / busy
