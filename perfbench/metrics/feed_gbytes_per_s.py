"""Host-to-device feed in set-up: the bytes of the host arrays over the
harness's own span around the configuration's `feed`, ended by
block_until_ready on every column."""


def read(obs: dict):
    feed = obs["feed"]
    if not feed or feed["seconds"] <= 0:
        return None
    return feed["bytes"] / feed["seconds"] / 1e9
