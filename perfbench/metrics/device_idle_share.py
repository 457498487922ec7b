"""1 - busy / traced window, in percent."""


def read(obs: dict):
    if not obs["trace"]:
        return None
    return 100.0 * (1.0 - obs["trace"]["busy_s"] / obs["trace"]["window_s"])
