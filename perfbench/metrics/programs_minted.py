"""Shard programs built (dense_rdd.program_mints()) over the whole run,
set-up included: none may be minted inside the window."""


def read(obs: dict):
    return obs["total"]["mints"] or None
